"""Compile the serving path's Pallas kernels for a described TPU v5e at
Mixtral-8x7B widths (bf16: D 4096, expert FF 14336, 32 query / 8 KV heads
of 128). Nothing runs: the TPU compiler, which is installed beside the CPU
backend, refuses here what the chip would refuse — an illegal block shape,
more scoped VMEM than a kernel may use — at no chip time.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention_fused,
                                            decode_attention_paged)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm

B, H, HKV, DH = 8, 32, 8, 128          # decode batch and Mixtral's heads
D, F, SLOTS = 4096, 14336, 16          # widths; 8 primaries + 8 shadows
MAX_SEQ, PROMPT, PAGE = 512, 128, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("capacity", [8, 1024])   # decode step / prefill
def test_moe_gemm_compiles(one_chip, capacity):
    bf = jnp.bfloat16
    _compile(lambda x, wg, wu, wd: moe_gemm(x, wg, wu, wd), one_chip,
             ((SLOTS, capacity, D), bf), ((SLOTS, D, F), bf),
             ((SLOTS, D, F), bf), ((SLOTS, F, D), bf))


def test_decode_attention_fused_compiles(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(decode_attention_fused, one_chip,
             ((B, H, DH), bf), ((B, MAX_SEQ, HKV, DH), bf),
             ((B, MAX_SEQ, HKV, DH), bf), ((B, MAX_SEQ), i32),
             ((B, HKV, DH), bf), ((B, HKV, DH), bf), ((B,), i32))


def test_decode_attention_paged_compiles(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    nblk = MAX_SEQ // PAGE
    pages = B * nblk + 1                   # parity budget + the null page
    _compile(decode_attention_paged, one_chip,
             ((B, H, DH), bf), ((pages, PAGE, HKV, DH), bf),
             ((pages, PAGE, HKV, DH), bf), ((pages, PAGE), i32),
             ((B, nblk), i32), ((B, HKV, DH), bf), ((B, HKV, DH), bf),
             ((B,), i32))


def test_flash_attention_compiles(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(flash_attention, one_chip,
             ((B, PROMPT, H, DH), bf), ((B, PROMPT, HKV, DH), bf),
             ((B, PROMPT, HKV, DH), bf), ((B, PROMPT), i32),
             ((B, PROMPT), i32))


@pytest.mark.parametrize("capacity", [16, 128])   # decode step / a chunk
def test_moe_gemm_compiles_at_qwen3_widths(one_chip, capacity):
    """Qwen3-30B-A3B's bank: D 2048, expert FF 768, 128 primary + 128
    shadow slots, with the per-slot token counts the served path passes
    (empty slots skip their MXU work)."""
    bf, i32 = jnp.bfloat16, jnp.int32
    d, f, slots = 2048, 768, 256
    _compile(lambda x, wg, wu, wd, n: moe_gemm(x, wg, wu, wd, counts=n),
             one_chip, ((slots, capacity, d), bf), ((slots, d, f), bf),
             ((slots, d, f), bf), ((slots, f, d), bf), ((slots,), i32))
