"""Chip smoke: the Tarragon serving path end to end on one TPU.

    python chip_smoke.py [--seed N]

Model: Mixtral-8x7B (arXiv:2401.04088) at its published widths -- d_model
4096, expert FF 14336, 32 query and 8 KV heads of 128, 8 experts top-2,
vocab 32000 -- in bfloat16, with random weights drawn from ``--seed``.
What was cut: depth, 32 layers -> 2. With 2 EWs each MoE layer serves 8
primary + 8 shadow expert slots, and 2 layers of that (6.3 GB of weights
plus the per-layer slot-bank gather) is what fits one 16 GB v5e chip.

Engine: 2 attention workers (AWs), 2 expert workers (EWs), batch 8,
max_seq 512, built and driven by ``repro.launch.serve`` (``build_server`` +
``serve``), the launcher's own path. Traffic: 8 requests of 128 prompt
tokens and 32 new tokens each, all arriving at t=0, tokens drawn from
``--seed``.

Phases, all in this one process (it holds the chip; it starts no other):
  kernels   each Pallas kernel of the served path, called through the
            served dispatch (kernels/ops.py), against its jnp reference
            (kernels/ref.py) at Mixtral's head and FFN widths
  serve     contiguous KV cache, failure-free
  failover  the same traffic with AW0 failing at t=0.5 and EW0 at t=0.8
            (virtual clock, 50 ms decode ticks) -- mid-decode. Its token
            streams must equal the failure-free ones: Tarragon's invariant
  paged     paged KV cache (128-token pages), 8 new tokens per request

Every served phase checks that all logits of every prefill and decode step
are finite and that the compiled steps hold the expected Pallas kernels
(``tpu_custom_call``). Any failed check exits non-zero. Wall times are
informational. The last line of output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.workloads import Request  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import build_server, serve  # noqa: E402
from repro.models.attention import blockwise_attention  # noqa: E402
from repro.serving.engine import EngineConfig  # noqa: E402
from repro.serving.scheduler import FailurePlan  # noqa: E402

NUM_LAYERS = 2
BATCH, MAX_SEQ, PROMPT, NEW, PAGED_NEW, PAGE = 8, 512, 128, 32, 8, 128
FAILURES = (FailurePlan(0.5, "aw", 0), FailurePlan(0.8, "ew", 0))
# max |kernel - reference| over max |reference|: bf16 operands and outputs
# against a float32 reference
KERNEL_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def smoke_config():
    cfg = get_config("mixtral_8x7b")
    return dataclasses.replace(cfg, name=cfg.name + f"-{NUM_LAYERS}l",
                               num_layers=NUM_LAYERS, dtype="bfloat16")


def pallas_kernels(compiled_text: str) -> set:
    """Names of the jitted wrappers whose Pallas calls the compiled program
    holds as TPU custom calls."""
    names = set()
    for line in compiled_text.splitlines():
        if "tpu_custom_call" in line:
            names.update(re.findall(r"jit\((\w+)\)/pallas_call", line))
    return names


class StepProbe:
    """Wraps one of the engine's jitted steps. Each call waits for its
    result (``block_until_ready``) and is timed; a device-side flag records
    whether its logits (output 0) were all finite; the first call's
    arguments are kept so that the step can be lowered again."""

    def __init__(self, step):
        self.step = step
        self.secs = []
        self.finite = []
        self.first = None

    def __call__(self, *args, **kw):
        if self.first is None:
            self.first = (args, kw)
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.step(*args, **kw))
        self.secs.append(time.perf_counter() - t0)
        self.finite.append(jnp.isfinite(out[0]).all())
        return out

    def all_finite(self) -> bool:
        return bool(jnp.stack(self.finite).all())

    def kernels(self) -> set:
        args, kw = self.first
        return pallas_kernels(self.step.lower(*args, **kw).compile().as_text())

    def timing(self) -> str:
        rest = self.secs[1:]
        tail = (f", then median {statistics.median(rest):.4f} s over "
                f"{len(rest)} calls" if rest else "")
        return f"first call {self.secs[0]:.3f} s (compile included){tail}"


# --------------------------------------------------------------------------
# kernels against their references
# --------------------------------------------------------------------------

def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_phase(cfg, seed: int) -> dict:
    bf = jnp.bfloat16
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    d, f = cfg.d_model, cfg.moe.d_ff
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(shape, scale=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(bf)

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    # kernels at the served (default) precision, references in float32
    errs = {}
    # decode attention, contiguous and paged, over the same content
    b, nblk = BATCH, MAX_SEQ // PAGE
    q = rnd((b, h, dh))
    ck, cv = rnd((b, MAX_SEQ, hkv, dh)), rnd((b, MAX_SEQ, hkv, dh))
    k1, v1 = rnd((b, hkv, dh)), rnd((b, hkv, dh))
    pos = jnp.arange(b, dtype=jnp.int32) * 61 + 37
    cpos = jnp.where(jnp.arange(MAX_SEQ)[None] < pos[:, None],
                     jnp.arange(MAX_SEQ)[None], -1).astype(jnp.int32)
    want = reference(kref.decode_attention_ref, q, ck, cv, cpos, k1, v1, pos)
    errs["decode_attention_fused"] = rel_err(
        kops.decode_attention(q, ck, cv, cpos, k1, v1, pos), want)

    def pages(a, fill):
        """[B, S, ...] as a pool of 1 + B*nblk pages; page 0 is the null
        page, slot i's block j is page 1 + i*nblk + j."""
        null = jnp.full((1, PAGE) + a.shape[2:], fill, a.dtype)
        return jnp.concatenate([null, a.reshape(b * nblk, PAGE, *a.shape[2:])])

    bt = 1 + jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    errs["decode_attention_paged"] = rel_err(kops.decode_attention_paged(
        q, pages(ck, 0), pages(cv, 0), pages(cpos, -1), bt, k1, v1, pos),
        want)
    # prefill flash attention (causal) over one prompt length
    qp = jnp.broadcast_to(jnp.arange(PROMPT, dtype=jnp.int32), (2, PROMPT))
    q, k, v = (rnd((2, PROMPT, h, dh)), rnd((2, PROMPT, hkv, dh)),
               rnd((2, PROMPT, hkv, dh)))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    errs["flash_attention"] = rel_err(
        kops.full_attention(q, k, v, qp, qp),
        reference(blockwise_attention, f32(q), f32(k), f32(v), qp, qp))
    # expert FFN over two slots at decode capacity
    x = rnd((2, BATCH, d))
    wg, wu = rnd((2, d, f), d ** -0.5), rnd((2, d, f), d ** -0.5)
    wd = rnd((2, f, d), f ** -0.5)
    errs["moe_gemm"] = rel_err(kops.expert_ffn(x, wg, wu, wd),
                               reference(kref.moe_gemm_ref, f32(x), wg, wu, wd))
    for name, err in sorted(errs.items()):
        print(f"[kernels] {name}: max|kernel - ref| / max|ref| = {err:.3e}")
        check(err <= KERNEL_TOL, f"{name} is {err:.3e} off its reference "
              f"(limit {KERNEL_TOL})")
    return errs


# --------------------------------------------------------------------------
# served phases
# --------------------------------------------------------------------------

def workload(seed: int, max_new: int):
    return [Request(f"r{i}", 0.0, PROMPT, max_new, seed=seed * 1000 + i)
            for i in range(BATCH)]


def serve_phase(name: str, cfg, seed: int, *, max_new: int, failures=(),
                kv_page_tokens: int = 0, decode_kernel: str) -> dict:
    ecfg = EngineConfig(max_batch=BATCH, max_seq=MAX_SEQ, num_aw=2, num_ew=2,
                        kv_page_tokens=kv_page_tokens)
    eng, orch = build_server(cfg, ecfg, seed=seed)
    prefill = eng._prefill = StepProbe(eng._prefill)
    decode = eng._decode = StepProbe(eng._decode)
    t0 = time.perf_counter()
    m = serve(eng, orch, workload(seed, max_new), failures=failures)
    wall = time.perf_counter() - t0

    param_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(eng.params))
    outputs = {rid: list(toks) for rid, toks in m.outputs.items()}
    events = sorted({e.kind for e in orch.events})
    print(f"[{name}] {len(m.finished)}/{BATCH} requests finished, "
          f"{sum(map(len, outputs.values()))} tokens, "
          f"{len(prefill.secs)} prefill + {len(decode.secs)} decode calls, "
          f"{eng.store.stats.restores} KV restores, events {events}")
    print(f"[{name}] wall {wall:.3f} s (informational)")
    print(f"[{name}] prefill {prefill.timing()} (informational)")
    print(f"[{name}] decode {decode.timing()} (informational)")
    check(sorted(m.finished) == sorted(outputs) and len(outputs) == BATCH
          and all(len(t) == max_new for t in outputs.values()),
          f"{name}: not every request finished with {max_new} tokens")
    check(prefill.all_finite() and decode.all_finite(),
          f"{name}: a served step produced non-finite logits")
    want = {"prefill": {"flash_attention", "moe_gemm"},
            "decode": {decode_kernel, "moe_gemm"}}
    for step, probe in (("prefill", prefill), ("decode", decode)):
        got = probe.kernels()
        print(f"[{name}] compiled {step} step: Pallas kernels {sorted(got)}")
        check(want[step] <= got, f"{name}: compiled {step} step lacks "
              f"{sorted(want[step] - got)} as tpu_custom_call")
    for f in failures:
        check(f"fail_{f.kind}" in events, f"{name}: {f.kind} failure was "
              "not injected")
    if any(f.kind == "aw" for f in failures):
        check(eng.store.stats.restores > 0,
              f"{name}: no request was restored after the AW failure")
    return {"outputs": outputs, "param_bytes": param_bytes}


def release():
    """Drop what a finished phase left on the device before the next engine
    draws its weights: two weight trees do not fit one chip."""
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def run_phases(cfg, seed: int):
    print(f"model: {cfg.name} d_model {cfg.d_model} expert_ff "
          f"{cfg.moe.d_ff} heads {cfg.num_heads}/{cfg.num_kv_heads}x"
          f"{cfg.head_dim_} experts {cfg.moe.num_experts} top-"
          f"{cfg.moe.top_k} vocab {cfg.vocab_size} layers {cfg.num_layers} "
          f"{cfg.dtype}")
    kernel_phase(cfg, seed)
    print(f"[kernels] live device bytes after: {release()}")
    ref = serve_phase("serve", cfg, seed, max_new=NEW,
                      decode_kernel="decode_attention_fused")
    print(f"parameter bytes: {ref['param_bytes']}")
    print(f"[serve] live device bytes after: {release()}")
    fail = serve_phase("failover", cfg, seed, max_new=NEW, failures=FAILURES,
                       decode_kernel="decode_attention_fused")
    same = fail["outputs"] == ref["outputs"]
    print(f"[failover] token streams identical to failure-free: {same}")
    check(same, "token streams differ under one AW and one EW failure")
    print(f"[failover] live device bytes after: {release()}")
    paged = serve_phase("paged", cfg, seed, max_new=PAGED_NEW,
                        kv_page_tokens=PAGE,
                        decode_kernel="decode_attention_paged")
    agree = sum(a == b for rid, toks in paged["outputs"].items()
                for a, b in zip(toks, ref["outputs"][rid]))
    print(f"[paged] tokens equal to the contiguous run's: {agree}/"
          f"{BATCH * PAGED_NEW} (informational: page-sized kv blocks "
          "accumulate in another order)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    kernels = os.environ.get("REPRO_KERNELS", "auto")
    if kernels not in ("auto", "pallas"):
        sys.exit(f"chip_smoke: REPRO_KERNELS={kernels!r} would bypass the "
                 "compiled Pallas kernels")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device['kind']} x{device['count']}")
    print(f"compile cache: {use_compile_cache()}")
    compile_secs = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_secs.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    try:
        run_phases(smoke_config(), args.seed)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    stats = dev.memory_stats() or {}
    print(f"peak device bytes in use: {stats.get('peak_bytes_in_use')} of "
          f"{stats.get('bytes_limit')}")
    print(f"backend compile seconds: {sum(compile_secs):.1f} over "
          f"{len(compile_secs)} programs")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
