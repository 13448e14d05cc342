"""Pallas TPU flash attention for the full-sequence (train/prefill) path.

Closes the dominant §Roofline headroom: the pure-jnp blockwise path
materializes [bq, bk] score tiles in HBM; this kernel keeps the online-
softmax state (m, l, acc) in VMEM scratch across the (sequential, innermost)
kv-block grid axis, so scores never leave VMEM.

Grid = (B, Hkv, Sq//bq, Sk//bk) — kv innermost, q-block output revisited.
Supports GQA (the G query heads of one kv head vs kv [bk, Dh]), causal
masking, sliding windows and score softcap via position operands (same mask
semantics as ``models.attention.blockwise_attention``, its oracle).

TPU block shapes (last two dims divisible by (8, 128) or whole): q, k, v and
the output are viewed as [B, S, heads*Dh] (free reshapes), so one grid step
reads the G*Dh lanes of its kv head's query group and the Dh lanes of the kv
head itself — legal for Dh a multiple of 128, or a single kv head. Query
positions ride as a [bq, 1] column, key positions as a [1, bk] row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, causal: bool, window: int, softcap: float, g: int,
                  dh: int):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = k_ref[0].astype(jnp.float32)             # [bk, Dh]
    v = v_ref[0].astype(jnp.float32)             # [bk, Dh]
    qpos = qpos_ref[0]                           # [bq, 1]
    kpos = kpos_ref[0]                           # [1, bk]
    mask = kpos >= 0
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window             # [bq, bk]

    for gi in range(g):                          # query heads of this kv head
        lanes = slice(gi * dh, (gi + 1) * dh)
        q = q_ref[0, :, lanes].astype(jnp.float32)   # [bq, Dh] (pre-scaled)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)          # [bq, bk]

        m_prev, l_prev = m_scr[gi], l_scr[gi]    # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_scr[gi] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[gi] = m_new
        l_scr[gi] = l_new
        acc_scr[gi] = acc_new

        @pl.when(ki == nk - 1)
        def _finalize():
            out = acc_new / jnp.maximum(l_new, 1e-30)
            out = jnp.where(l_new > 0, out, 0.0)
            o_ref[0, :, lanes] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, q_pos, k_pos, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q: [B,Sq,H,Dh]; k,v: [B,Sk,Hkv,Dh]; *_pos: [B,Sq]/[B,Sk] int32
    (-1 = invalid). Returns [B,Sq,H,Dh]."""
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv

    def fit(block, s):
        blk = min(block, s)
        while s % blk:
            blk //= 2
        return max(blk, 1)

    bq, bk = fit(block_q, sq), fit(block_k, sk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    grid = (b, hkv, sq // bq, sk // bk)
    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               softcap=softcap, g=g, dh=dh)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, g * dh),
                         lambda bi, hi, qi, ki: (bi, qi, hi)),
            pl.BlockSpec((1, bk, dh), lambda bi, hi, qi, ki: (bi, ki, hi)),
            pl.BlockSpec((1, bk, dh), lambda bi, hi, qi, ki: (bi, ki, hi)),
            pl.BlockSpec((1, bq, 1), lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, bq, g * dh),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h * dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qs.reshape(b, sq, h * dh), k.reshape(b, sk, hkv * dh),
      v.reshape(b, sk, hkv * dh), q_pos.astype(jnp.int32).reshape(b, sq, 1),
      k_pos.astype(jnp.int32).reshape(b, 1, sk))
    return out.reshape(b, sq, h, dh)
