"""Operations and bytes the mathematics needs, from a configuration's widths.

These count the work of the published model, not of the program's choices:
no padded rows, no capacity slots, no shadow or empty expert slots, no
masked attention blocks. A kernel that skips waste therefore reads closer to
its roofline; one that does waste reads further from it, and none can read
over 100% unless the count or the kernel time is wrong.

All widths come from ``harness.weights.dims``; bytes are of bf16 (2 bytes)
operands, the dtype the configurations serve.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from harness.weights import dims

BYTES = 2   # bf16


def attention_proj_flops(c: dict) -> int:
    """q, k, v and output projections of one token in one layer."""
    d = dims(c)
    return 2 * d["D"] * d["Dh"] * (2 * d["H"] + 2 * d["Hkv"])


def expert_flops(c: dict) -> int:
    """One token through one SwiGLU expert (gate, up, down)."""
    d = dims(c)
    return 2 * 3 * d["D"] * d["F"]


def score_flops(c: dict, ctx: int) -> int:
    """q.k and p.v of one query over ``ctx`` keys in one layer."""
    d = dims(c)
    return 4 * d["H"] * d["Dh"] * ctx


def token_flops(c: dict, ctx: int, logits: bool) -> int:
    """Model FLOPs of one token that attends over ``ctx`` keys (itself
    included): every layer's projections, router, its top-k experts and
    attention scores, plus the output head when it yields logits."""
    d = dims(c)
    per_layer = attention_proj_flops(c) + 2 * d["D"] * d["E"] + \
        d["K"] * expert_flops(c) + score_flops(c, ctx)
    head = 2 * d["V"] * d["D"] if logits else 0
    return d["L"] * per_layer + head


def decode_step_flops(c: dict, ctx: Iterable[int]) -> int:
    """One decode step: each active row yields logits at its context."""
    return sum(token_flops(c, int(n), True) for n in ctx)


def chunk_flops(c: dict, rows: Iterable[Tuple[int, int]]) -> int:
    """One prefill-chunk call: rows of (first position, real tokens); the
    token at position p attends over p + 1 keys; no logits."""
    d = dims(c)
    total = 0
    for start, n in rows:
        # sum over p in [start, start + n) of score_flops(p + 1)
        keys = n * start + n * (n + 1) // 2
        total += n * (token_flops(c, 0, False)) + \
            d["L"] * score_flops(c, 1) * keys
    return total


def distinct_experts(c: dict, tokens: int) -> float:
    """Expected number of distinct experts ``tokens`` tokens route to in one
    layer, each choosing K of E uniformly: E (1 - (1 - K/E)^tokens)."""
    d = dims(c)
    return d["E"] * (1.0 - (1.0 - d["K"] / d["E"]) ** tokens)


def moe_gemm_cost(c: dict, tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the expert FFN of one layer for ``tokens`` routed
    tokens: K rows each; the weights of the experts they reach, read once;
    the rows read and written."""
    d = dims(c)
    rows = tokens * d["K"]
    flops = rows * expert_flops(c)
    weights = distinct_experts(c, tokens) * 3 * d["D"] * d["F"] * BYTES
    return float(flops), float(weights + 2 * rows * d["D"] * BYTES)


def decode_attention_cost(c: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode attention: each row reads the
    keys and values of its ``ctx`` positions once."""
    d = dims(c)
    ctx = [int(n) for n in ctx]
    flops = sum(score_flops(c, n) for n in ctx)
    kv = sum(ctx) * 2 * d["Hkv"] * d["Dh"] * BYTES
    q_out = len(ctx) * 2 * d["H"] * d["Dh"] * BYTES
    return float(flops), float(kv + q_out)


def flash_attention_cost(c: dict, rows: Iterable[Tuple[int, int]]
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's causal chunk attention: a row of n
    real tokens from position ``start`` reads start + n keys and values and
    its n queries, and writes n outputs."""
    d = dims(c)
    flops = bytes_ = 0
    for start, n in rows:
        keys = n * start + n * (n + 1) // 2
        flops += score_flops(c, 1) * keys
        bytes_ += (start + n) * 2 * d["Hkv"] * d["Dh"] * BYTES + \
            n * 2 * d["H"] * d["Dh"] * BYTES
    return float(flops), float(bytes_)


def least_time(flops: float, bytes_: float, peaks: dict) -> float:
    """The roofline: the longer of compute at peak and traffic at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
