"""Pallas TPU grouped MoE expert-FFN kernel.

Computes, independently per expert slot p:
    y[p] = (act(x[p] @ w_gate[p]) * (x[p] @ w_up[p])) @ w_down[p]

This is the EW-side hot loop (App. B of the paper: expert GEMM efficiency vs
batch size is what motivates layer-wise batching). TPU-native tiling:

  * grid = (P, C // block_c, F // block_f); the ff-tile axis is innermost and
    accumulates into the output block (output index map ignores the f axis,
    so the block is revisited and we += across f tiles).
  * every matmul tile is MXU-shaped: [block_c, D] @ [D, block_f] and
    [block_c, block_f] @ [block_f, D], with block_c/block_f multiples of 128
    when the shapes allow.
  * the gate/up intermediate only ever exists as a [block_c, block_f] VMEM
    tile — the full [C, F] hidden activation is never materialized.

Empty slots (shadow experts with zero routed tokens) contribute zero compute
*work* on real hardware via the zero one-hot rows — the kernel itself is
shape-static, matching the dry-run FLOP accounting discussed in DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_ffn_kernel(counts_ref, x_ref, wg_ref, wu_ref, wd_ref, y_ref,
                    *, act: str, gated: bool):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # Inactive shadow / padded slots receive zero routed tokens: skip their
    # MXU work entirely (the paper's "shadows consume no compute", §5.3 /
    # App. D). counts is scalar-prefetched per slot.
    pi = pl.program_id(0)

    @pl.when(counts_ref[pi] > 0)
    def _compute():
        _moe_ffn_body(x_ref, wg_ref, wu_ref, wd_ref, y_ref, act=act,
                      gated=gated)


def _moe_ffn_body(x_ref, wg_ref, wu_ref, wd_ref, y_ref, *, act: str,
                  gated: bool):
    # operands enter the MXU in their stored dtype (bf16 on the chip) and
    # accumulate in f32: no weight tile is upcast in VMEM
    x = x_ref[0]                                 # [bc, D]
    up = jax.lax.dot_general(x, wu_ref[0], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    fn = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    if gated:
        gate = jax.lax.dot_general(x, wg_ref[0], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        hidden = fn(gate) * up
    else:
        hidden = fn(up)
    wd = wd_ref[0]                               # [bf, D]
    y_ref[0] += jax.lax.dot_general(hidden.astype(wd.dtype), wd,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)


# Scoped VMEM the pipelined tiles may take: v5e's default scoped limit is
# 16 MiB; 4 MiB stay free for the kernel's own temporaries.
VMEM_TILE_BUDGET = 12 * 2**20


def _tile_bytes(bc: int, bf: int, d: int, x_bytes: int,
                w_bytes: int) -> int:
    """Double-buffered VMEM of one grid step: the x tile, the three weight
    tiles of [D, bf] / [bf, D] (an ungated FFN's placeholder gate is
    pipelined too), and the f32 output tile."""
    return 2 * (bc * d * x_bytes + 3 * d * bf * w_bytes + bc * d * 4)


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def moe_gemm(x, w_gate, w_up, w_down, *, counts=None, act: str = "silu",
             block_c: int = 128, block_f: int = 512,
             interpret: bool = False):
    """x: [P,C,D]; w_gate/w_up: [P,D,F]; w_down: [P,F,D] -> y [P,C,D].

    ``counts`` [P] int32: routed tokens per slot — slots with 0 skip all
    compute (inactive shadows / pad slots). None = assume all active."""
    p_slots, c, d = x.shape
    f = w_up.shape[-1]
    if counts is None:
        counts = jnp.ones((p_slots,), jnp.int32)
    bc = min(block_c, c)
    while c % bc:
        bc //= 2
    bc = max(bc, 1)
    gated = w_gate is not None
    # largest ff tile that divides F and whose double-buffered tiles fit
    # the scoped VMEM budget (Mixtral bf16, bc=128: bf=128 takes 12 MiB)
    bf = min(block_f, f)
    while bf > 1 and (f % bf or _tile_bytes(
            bc, bf, d, x.dtype.itemsize,
            w_up.dtype.itemsize) > VMEM_TILE_BUDGET):
        bf //= 2
    kernel = functools.partial(_moe_ffn_kernel, act=act, gated=gated)
    if not gated:
        w_gate = w_up  # placeholder operand, never read

    grid = (p_slots, c // bc, f // bf)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # counts [P]
            pl.BlockSpec((1, bc, d), lambda pi, ci, fi: (pi, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda pi, ci, fi: (pi, 0, fi)),
            pl.BlockSpec((1, d, bf), lambda pi, ci, fi: (pi, 0, fi)),
            pl.BlockSpec((1, bf, d), lambda pi, ci, fi: (pi, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda pi, ci, fi: (pi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((p_slots, c, d), jnp.float32),
        interpret=interpret,
    )(counts.astype(jnp.int32), x, w_gate, w_up, w_down)
    return y.astype(x.dtype)
