"""Pallas TPU flash-decode kernel (GQA, one query token vs a long KV cache).

This is the latency-critical op of the decode phase (§2 of the paper: TBT is
the user-visible metric; decode dominates recovery concern). The kernel
streams the KV cache HBM->VMEM in blocks and keeps an online-softmax running
(m, l, acc) per (batch, kv-head) so live VMEM is O(block) regardless of the
32k/500k cache length.

Layout / tiling decisions (TPU-native, not a CUDA port):
  * grid = (B, Hkv, Sc // block_k); the kv-block axis is innermost, i.e. the
    sequential accumulation axis on TPU.
  * q block [G, Dh] (G = H/Hkv grouped queries) hits the MXU as a skinny
    matmul against [block_k, Dh] key tiles; Dh is padded to 128 by layout.
  * two variants share the block loop: ``decode_attention_partial`` emits
    the softmax partials (m, l, acc) for callers that combine externally
    (seq-sharded caches psum-combine them), and ``decode_attention_fused``
    — the serving decode step's kernel — keeps the partials in VMEM
    scratch and, on the last kv block, folds the current token's
    self-attention term and the final normalization in-kernel, so one
    pallas_call returns the finished [B,H,Dh] attention output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, cpos_ref,
                        m_ref, l_ref, acc_ref,
                        *, window: int, softcap: float, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [G, Dh] (pre-scaled)
    k = k_ref[0, :, 0].astype(jnp.float32)       # [bk, Dh]
    v = v_ref[0, :, 0].astype(jnp.float32)       # [bk, Dh]
    cpos = cpos_ref[0]                           # [bk] int32
    pos = pos_ref[0]                             # scalar int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, bk]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = (cpos >= 0) & (cpos <= pos)
    if window:
        mask &= cpos > (pos - window)
    s = jnp.where(mask[None, :], s, NEG_INF)

    m_prev = m_ref[0, 0]                         # [G]
    l_prev = l_ref[0, 0]
    acc_prev = acc_ref[0, 0]                     # [G, Dh]

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new
    acc_ref[0, 0] = acc_new


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block_k",
                                             "interpret"))
def decode_attention_partial(q, ck, cv, cpos, pos, *, window: int = 0,
                             softcap: float = 0.0, block_k: int = 512,
                             interpret: bool = False):
    """Online-softmax partials of q against the KV cache.

    q: [B,H,Dh] (unscaled); ck/cv: [B,Sc,Hkv,Dh]; cpos: [B,Sc]; pos: [B].
    Returns (m, l, acc): [B,Hkv,G], [B,Hkv,G], [B,Hkv,G,Dh] — fp32.
    """
    b, h, dh = q.shape
    sc, hkv = ck.shape[1], ck.shape[2]
    g = h // hkv
    bk = min(block_k, sc)
    while sc % bk:
        bk //= 2
    bk = max(bk, 1)

    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qs = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, dh)

    grid = (b, hkv, sc // bk)
    kernel = functools.partial(_decode_attn_kernel, window=window,
                               softcap=softcap, block_k=bk)
    m, l, acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ki: (bi,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, dh), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, hi, ki: (bi, ki, hi, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, hi, ki: (bi, ki, hi, 0)),
            pl.BlockSpec((1, bk), lambda bi, hi, ki: (bi, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g), lambda bi, hi, ki: (bi, hi, 0)),
            pl.BlockSpec((1, 1, g), lambda bi, hi, ki: (bi, hi, 0)),
            pl.BlockSpec((1, 1, g, dh), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
        ],
        interpret=interpret,
    )(pos.astype(jnp.int32), qs, ck, cv, cpos)
    return m, l, acc


# --------------------------------------------------------------------------
# fused variants: cache blocks + self-attention fold + normalize, one call
# --------------------------------------------------------------------------
#
# TPU blocks must have their last two dims divisible by (8, 128) or equal to
# the whole array dim. The caches are token-major [B|P, S, Hkv, Dh]; the
# wrappers view them as [B|P, S, Hkv*Dh] (a free reshape), and each grid step
# DMAs one kv head's Dh lanes of a block of tokens, so the K/V blocks are
# (block, Dh): legal for Dh a multiple of 128, or a single kv head. Stored
# positions ride as [B|P, 1, S] rows; ``pos`` and the paged block table are
# scalar-prefetched into SMEM.

def _decode_attn_fused_kernel(*refs, n_prefetch: int, window: int,
                              softcap: float, nk: int):
    """Online-softmax block loop with the running (m, l, acc) in VMEM
    scratch — persistent across the sequential kv-block grid axis. The
    LAST block folds the current token's (k1, v1) contribution and writes
    the normalized output. Shared by the contiguous (``n_prefetch`` = 1:
    pos) and paged (``n_prefetch`` = 2: block table, pos) variants, whose
    index maps alone differ — so at block_k == page_tokens the two are
    bit-identical on identical logical content."""
    pos_ref = refs[n_prefetch - 1]
    (q_ref, k_ref, v_ref, cpos_ref, k1_ref, v1_ref, o_ref,
     m_ref, l_ref, acc_ref) = refs[n_prefetch:]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [G, Dh] (pre-scaled)
    k = k_ref[0].astype(jnp.float32)             # [bk, Dh]
    v = v_ref[0].astype(jnp.float32)             # [bk, Dh]
    cpos = cpos_ref[0]                           # [1, bk] int32
    pos = pos_ref[pl.program_id(0)]              # scalar int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, bk]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = (cpos >= 0) & (cpos <= pos)
    if window:
        mask &= cpos > (pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        k1 = k1_ref[0].astype(jnp.float32)       # [1, Dh]
        v1 = v1_ref[0].astype(jnp.float32)       # [1, Dh]
        s_self = jnp.sum(q * k1, axis=-1, keepdims=True)   # [G, 1]
        if softcap:
            s_self = jnp.tanh(s_self / softcap) * softcap
        m_f = jnp.maximum(m_ref[...], s_self)
        corr_f = jnp.exp(m_ref[...] - m_f)
        p_self = jnp.exp(s_self - m_f)
        l_f = l_ref[...] * corr_f + p_self
        acc_f = acc_ref[...] * corr_f + p_self * v1
        o_ref[0, 0] = acc_f / jnp.maximum(l_f, 1e-30)


def _fused_operands(q, k1, v1, hkv):
    """Pre-scaled grouped queries [B,Hkv,G,Dh] and the current token's
    K/V as [B,1,Hkv*Dh] rows (one kv head = one Dh-lane block)."""
    b, h, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qs = (q.astype(jnp.float32) * scale).reshape(b, hkv, h // hkv, dh)
    return (qs, k1.reshape(b, 1, hkv * dh), v1.reshape(b, 1, hkv * dh))


def _fused_scratch(g, dh):
    return [pltpu.VMEM((g, 1), jnp.float32),     # running max m
            pltpu.VMEM((g, 1), jnp.float32),     # running denom l
            pltpu.VMEM((g, dh), jnp.float32)]    # running numerator acc


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention_paged(q, pk, pv, ppos, bt, k1, v1, pos, *,
                           softcap: float = 0.0, interpret: bool = False):
    """Fused GQA decode attention over a paged KV cache.

    q: [B,H,Dh] (unscaled); pk/pv: [P,pt,Hkv,Dh] physical page pools;
    ppos: [P,pt] stored positions (-1 = empty); bt: [B,nblk] int32 block
    table (0 = the reserved null page); k1/v1: [B,Hkv,Dh]; pos: [B].
    Full attention only (paged mode has no sliding-window layers).
    Returns [B,H,Dh] in q's dtype.

    The kv-block grid axis walks the slot's block table and each block's
    index map resolves the physical page, so the pages stream HBM->VMEM
    in logical order without materializing a gathered copy. Unmapped
    blocks resolve to the null page whose positions are all -1 — they
    mask to an exact no-op, identical to an empty contiguous region.
    """
    b, h, dh = q.shape
    npages, pt, hkv = pk.shape[0], pk.shape[1], pk.shape[2]
    nk = bt.shape[1]
    g = h // hkv
    qs, k1, v1 = _fused_operands(q, k1, v1, hkv)

    kernel = functools.partial(_decode_attn_fused_kernel, n_prefetch=2,
                               window=0, softcap=softcap, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, hi, ki, bt_ref, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, pt, dh),
                         lambda bi, hi, ki, bt_ref, pos_ref:
                         (bt_ref[bi, ki], 0, hi)),
            pl.BlockSpec((1, pt, dh),
                         lambda bi, hi, ki, bt_ref, pos_ref:
                         (bt_ref[bi, ki], 0, hi)),
            pl.BlockSpec((1, 1, pt),
                         lambda bi, hi, ki, bt_ref, pos_ref:
                         (bt_ref[bi, ki], 0, 0)),
            pl.BlockSpec((1, 1, dh),
                         lambda bi, hi, ki, bt_ref, pos_ref: (bi, 0, hi)),
            pl.BlockSpec((1, 1, dh),
                         lambda bi, hi, ki, bt_ref, pos_ref: (bi, 0, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, hi, ki, bt_ref, pos_ref:
                               (bi, hi, 0, 0)),
        scratch_shapes=_fused_scratch(g, dh),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
        interpret=interpret,
    )(bt.astype(jnp.int32), pos.astype(jnp.int32), qs,
      pk.reshape(npages, pt, hkv * dh), pv.reshape(npages, pt, hkv * dh),
      ppos.reshape(npages, 1, pt), k1, v1)
    return out.reshape(b, h, dh).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block_k",
                                             "interpret"))
def decode_attention_fused(q, ck, cv, cpos, k1, v1, pos, *, window: int = 0,
                           softcap: float = 0.0, block_k: int = 512,
                           interpret: bool = False):
    """Fully fused GQA decode attention: cache blocks + the current token's
    self-attention + normalization in ONE pallas_call.

    q: [B,H,Dh] (unscaled); ck/cv: [B,Sc,Hkv,Dh]; cpos: [B,Sc];
    k1/v1: [B,Hkv,Dh]; pos: [B]. Returns [B,H,Dh] in q's dtype.
    """
    b, h, dh = q.shape
    sc, hkv = ck.shape[1], ck.shape[2]
    g = h // hkv
    bk = min(block_k, sc)
    while sc % bk:
        bk //= 2
    bk = max(bk, 1)
    nk = sc // bk
    qs, k1, v1 = _fused_operands(q, k1, v1, hkv)

    kernel = functools.partial(_decode_attn_fused_kernel, n_prefetch=1,
                               window=window, softcap=softcap, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, hi, ki, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bk, dh), lambda bi, hi, ki, pos_ref: (bi, ki, hi)),
            pl.BlockSpec((1, bk, dh), lambda bi, hi, ki, pos_ref: (bi, ki, hi)),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, ki, pos_ref: (bi, 0, ki)),
            pl.BlockSpec((1, 1, dh), lambda bi, hi, ki, pos_ref: (bi, 0, hi)),
            pl.BlockSpec((1, 1, dh), lambda bi, hi, ki, pos_ref: (bi, 0, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, hi, ki, pos_ref: (bi, hi, 0, 0)),
        scratch_shapes=_fused_scratch(g, dh),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), qs, ck.reshape(b, sc, hkv * dh),
      cv.reshape(b, sc, hkv * dh), cpos.reshape(b, 1, sc), k1, v1)
    return out.reshape(b, h, dh).astype(q.dtype)
