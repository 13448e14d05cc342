"""GQA attention: blockwise (flash-style) full attention for train/prefill and
single-token decode against a (optionally ring-buffered sliding-window) KV
cache.

Cache layout per attention layer:
    {"k": [B, Sc, Hkv, Dh], "v": [B, Sc, Hkv, Dh], "pos": [B, Sc] int32}
``pos`` holds the absolute position stored in each slot (-1 = empty). For
sliding-window layers Sc == window and slots are used as a ring buffer, which
is what makes ``long_500k`` memory-feasible for SWA architectures.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, rmsnorm, rmsnorm_init, apply_rope

NEG_INF = -1e30

# Cached (prefill/chunk) attention pins the KV block size of the online
# softmax: a fully-masked KV block is an exact no-op (m/l/acc unchanged),
# so with a common block size the accumulation order — and therefore the
# float result — is identical whether a token's prefix is scanned inside a
# bucket-padded whole-prompt prefill or inside a full-cache chunk call.
# This is what makes chunked prefill bit-identical to whole-prompt prefill.
PREFILL_BLOCK_K = 16


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, cross: bool = False):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh),
        "wk": dense_init(ks[1], d, hkv * dh),
        "wv": dense_init(ks[2], d, hkv * dh),
        "wo": dense_init(ks[3], h * dh, d),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * dh,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * dh,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * dh,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(dh)
        p["k_norm"] = rmsnorm_init(dh)
    return p


def _project_q(cfg, params, x):
    b, s, _ = x.shape
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim_)
    if "q_norm" in params:
        with jax.named_scope("qk_norm"):
            q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
    return q


def _project_kv(cfg, params, x):
    b, s, _ = x.shape
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bk" in params:
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim_)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim_)
    if "k_norm" in params:
        with jax.named_scope("qk_norm"):
            k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return k, v


def _softcap_scores(s, cap: float):
    if cap:
        s = jnp.tanh(s / cap) * cap
    return s


# --------------------------------------------------------------------------
# blockwise full attention (flash-style, pure JAX — ref for the Pallas kernel)
# --------------------------------------------------------------------------

def _pick_block(s: int, target: int = 512) -> int:
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def blockwise_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                        softcap: float = 0.0, causal: bool = True,
                        block_q: int = 0, block_k: int = 0):
    """q: [B,Sq,H,Dh]; k,v: [B,Sk,Hkv,Dh]; *_pos: [B,Sq]/[B,Sk] (-1 = invalid).

    Online-softmax over KV blocks; O(Sq * block_k) live memory per block pair.
    """
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = block_q or _pick_block(sq)
    bk = block_k or _pick_block(sk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))

    qs = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * scale

    def q_block_body(qi):
        qb = jax.lax.dynamic_slice_in_dim(qs, qi * bq, bq, axis=1)
        qpb = jax.lax.dynamic_slice_in_dim(q_pos, qi * bq, bq, axis=1)

        def kv_block_body(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, ki * bk, bk, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * bk, bk, axis=1)
            kpb = jax.lax.dynamic_slice_in_dim(k_pos, ki * bk, bk, axis=1)
            # scores: [B, bq, Hkv, G, bk]
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qb, kb.astype(jnp.float32))
            s = _softcap_scores(s, softcap)
            mask = kpb[:, None, :] >= 0
            if causal:
                mask &= kpb[:, None, :] <= qpb[:, :, None]
            if window:
                mask &= kpb[:, None, :] > qpb[:, :, None] - window
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, bq, hkv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, bq, hkv, g), jnp.float32)
        a0 = jnp.zeros((b, bq, hkv, g, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block_body, (m0, l0, a0), jnp.arange(sk // bk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # fully-masked rows (invalid q) -> zero
        out = jnp.where((l > 0)[..., None], out, 0.0)
        return out.reshape(b, bq, h, dh)

    blocks = jax.lax.map(q_block_body, jnp.arange(sq // bq))
    # [nq, B, bq, H, Dh] -> [B, Sq, H, Dh]
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, h, dh)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# cache management
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, window: int = 0,
               dtype=None):
    sc = min(window, max_seq) if window else max_seq
    dh, hkv = cfg.head_dim_, cfg.num_kv_heads
    dt = dtype or cfg.jnp_dtype
    return {
        "k": jnp.zeros((batch, sc, hkv, dh), dt),
        "v": jnp.zeros((batch, sc, hkv, dh), dt),
        "pos": jnp.full((batch, sc), -1, jnp.int32),
    }


def cache_write_prefill(cache, k, v, positions):
    """Write prefill K/V [B,S,...] into cache (keeping last Sc if S > Sc)."""
    sc = cache["k"].shape[1]
    s = k.shape[1]
    if s <= sc:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1)
        cp = jax.lax.dynamic_update_slice_in_dim(cache["pos"], positions.astype(jnp.int32), 0, 1)
        return {"k": ck, "v": cv, "pos": cp}
    # sliding window: ring-place the last sc entries at slot = pos % sc
    k, v, positions = k[:, -sc:], v[:, -sc:], positions[:, -sc:]
    slots = positions % sc
    bidx = jnp.arange(k.shape[0])[:, None]
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    cp = cache["pos"].at[bidx, slots].set(positions.astype(jnp.int32))
    return {"k": ck, "v": cv, "pos": cp}


def cache_write_chunk(cache, k, v, positions):
    """Write a prefill chunk's K/V [B,C,...] at absolute ``positions``
    [B,C] into an existing cache. Entries with position -1 (chunk padding
    or rows not participating in this chunk call) are left untouched, so
    the same call can extend some rows' prompts while other rows hold live
    decode state."""
    sc = cache["k"].shape[1]
    valid = positions >= 0
    # invalid entries scatter out of bounds and are dropped, so they can
    # never collide with a real write targeting the same slot
    slots = jnp.where(valid, positions % sc, sc)
    bidx = jnp.arange(k.shape[0])[:, None]
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype),
                                        mode="drop")
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype),
                                        mode="drop")
    cp = cache["pos"].at[bidx, slots].set(positions.astype(jnp.int32),
                                          mode="drop")
    return {"k": ck, "v": cv, "pos": cp}


def cache_write_token(cache, k1, v1, pos, window: int = 0):
    """Write one token's K/V [B,1,...] at absolute position pos [B].
    Rows with pos < 0 (slots not decoding this step — empty, or still
    mid-chunked-prefill) scatter out of bounds and are dropped, so a
    shared decode step never scribbles into a slot it does not own."""
    sc = cache["k"].shape[1]
    slot = (pos % sc) if window else jnp.minimum(pos, sc - 1)
    slot = jnp.where(pos >= 0, slot, sc)
    bidx = jnp.arange(k1.shape[0])
    ck = cache["k"].at[bidx, slot].set(k1[:, 0].astype(cache["k"].dtype),
                                       mode="drop")
    cv = cache["v"].at[bidx, slot].set(v1[:, 0].astype(cache["v"].dtype),
                                       mode="drop")
    cp = cache["pos"].at[bidx, slot].set(pos.astype(jnp.int32), mode="drop")
    return {"k": ck, "v": cv, "pos": cp}


# --------------------------------------------------------------------------
# paged cache (block tables over a physical page pool)
# --------------------------------------------------------------------------
#
# Paged layer cache: {"k": [P, pt, Hkv, Dh], "v": [P, pt, Hkv, Dh],
# "pos": [P, pt] int32} — P physical pages of pt tokens each — plus one
# block table ``bt`` [B, nblk] int32 shared by all layers mapping logical
# block j of slot b to a physical page. Page 0 is reserved as the null
# page: never allocated, its ``pos`` stays -1 forever, and every unmapped
# block-table entry points at it, so gathers always read a valid page and
# unmapped regions are masked exactly like an empty contiguous cache.
# With nblk * pt == Sc the gathered view reproduces the contiguous layout
# element-for-element, which is what makes the paged engine bit-identical.


def paged_view(cache, bt):
    """Gather the contiguous [B, nblk*pt, ...] view of a paged layer cache
    through the block table. Stale K/V under pos==-1 entries (recycled or
    null pages) is harmless: masked scores are the constant NEG_INF before
    any value is read, same as a zeroed contiguous cache."""
    b, nblk = bt.shape
    pt = cache["k"].shape[1]
    flat = bt.reshape(-1)
    k = cache["k"][flat].reshape(b, nblk * pt, *cache["k"].shape[2:])
    v = cache["v"][flat].reshape(b, nblk * pt, *cache["v"].shape[2:])
    pos = cache["pos"][flat].reshape(b, nblk * pt)
    return {"k": k, "v": v, "pos": pos}


def paged_write_chunk(cache, bt, k, v, positions):
    """Paged twin of cache_write_chunk: scatter chunk K/V [B,C,...] at
    absolute ``positions`` [B,C] into physical pages via the block table.
    Invalid entries (-1 padding) and entries whose block is unmapped
    (page 0 — only possible if the host failed to pre-allocate) scatter
    out of bounds and are dropped."""
    p, pt = cache["k"].shape[0], cache["k"].shape[1]
    nblk = bt.shape[1]
    valid = positions >= 0
    spos = positions % (nblk * pt)
    blk = jnp.where(valid, spos // pt, 0)
    page = jnp.take_along_axis(bt, blk, axis=1)
    page = jnp.where(valid & (page > 0), page, p)
    off = spos % pt
    ck = cache["k"].at[page, off].set(k.astype(cache["k"].dtype),
                                      mode="drop")
    cv = cache["v"].at[page, off].set(v.astype(cache["v"].dtype),
                                      mode="drop")
    cp = cache["pos"].at[page, off].set(positions.astype(jnp.int32),
                                        mode="drop")
    return {"k": ck, "v": cv, "pos": cp}


def paged_write_token(cache, bt, k1, v1, pos):
    """Paged twin of cache_write_token (full-attention layers only): write
    one token's K/V [B,1,...] at absolute position pos [B] through the
    block table. Rows with pos < 0 drop, mirroring the contiguous path."""
    p, pt = cache["k"].shape[0], cache["k"].shape[1]
    s = bt.shape[1] * pt
    spos = jnp.minimum(jnp.maximum(pos, 0), s - 1)
    blk = spos // pt
    page = bt[jnp.arange(bt.shape[0]), blk]
    page = jnp.where((pos >= 0) & (page > 0), page, p)
    off = spos % pt
    ck = cache["k"].at[page, off].set(k1[:, 0].astype(cache["k"].dtype),
                                      mode="drop")
    cv = cache["v"].at[page, off].set(v1[:, 0].astype(cache["v"].dtype),
                                      mode="drop")
    cp = cache["pos"].at[page, off].set(pos.astype(jnp.int32), mode="drop")
    return {"k": ck, "v": cv, "pos": cp}


# --------------------------------------------------------------------------
# layer-level apply
# --------------------------------------------------------------------------

def attn_full(cfg: ModelConfig, params, x, positions, *, window: int = 0,
              causal: bool = True, cache: Optional[dict] = None):
    """Train / prefill path. Returns (out [B,S,D], updated cache or None).

    Prefill (cache is not None) pins the KV block size so its accumulation
    order matches the chunked path exactly; train keeps the auto-sized
    blocks."""
    q = _project_q(cfg, params, x)
    k, v = _project_kv(cfg, params, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    from repro.kernels import ops as kops
    bk = _pick_block(k.shape[1], PREFILL_BLOCK_K) if cache is not None else 0
    with jax.named_scope("attention"):
        out = kops.full_attention(
            q, k, v, positions, positions, window=window,
            softcap=cfg.attn_softcap, causal=causal, block_k=bk)
    out = out.reshape(*x.shape[:2], -1) @ params["wo"]
    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            new_cache = cache_write_prefill(cache, k, v, positions)
    return out, new_cache


def attn_chunk(cfg: ModelConfig, params, x, cache, positions, *,
               window: int = 0):
    """Chunked-prefill path: x [B,C,D] extends each row's sequence at
    absolute ``positions`` [B,C] (-1 = chunk padding / row not in this
    chunk). The chunk's K/V are written into the cache first, then the
    chunk queries attend over the whole updated cache — causal masking by
    stored position covers both the committed prefix and the chunk itself.
    Returns (out [B,C,D], new_cache)."""
    q = _project_q(cfg, params, x)
    k, v = _project_kv(cfg, params, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        new_cache = cache_write_chunk(cache, k, v, positions)
    from repro.kernels import ops as kops
    with jax.named_scope("attention"):
        out = kops.full_attention(
            q, new_cache["k"], new_cache["v"], positions, new_cache["pos"],
            window=window, softcap=cfg.attn_softcap, causal=True,
            block_k=_pick_block(new_cache["k"].shape[1], PREFILL_BLOCK_K))
    out = out.reshape(*x.shape[:2], -1) @ params["wo"]
    return out, new_cache


def attn_chunk_paged(cfg: ModelConfig, params, x, cache, bt, positions):
    """Chunked-prefill over a paged layer cache: write the chunk's K/V
    through the block table, then attend over the gathered contiguous
    view. Same pinned KV block size as attn_chunk, so the accumulation
    order — and the float result — matches the contiguous engine exactly.
    Paged mode is full-attention only (window == 0)."""
    q = _project_q(cfg, params, x)
    k, v = _project_kv(cfg, params, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        new_cache = paged_write_chunk(cache, bt, k, v, positions)
    from repro.kernels import ops as kops
    with jax.named_scope("attention"):
        view = paged_view(new_cache, bt)
        out = kops.full_attention(
            q, view["k"], view["v"], positions, view["pos"],
            window=0, softcap=cfg.attn_softcap, causal=True,
            block_k=_pick_block(view["k"].shape[1], PREFILL_BLOCK_K))
    out = out.reshape(*x.shape[:2], -1) @ params["wo"]
    return out, new_cache


def attn_decode_paged(cfg: ModelConfig, params, x, cache, bt, pos):
    """Single-token decode over a paged layer cache. The attention itself
    gathers K/V pages through the block table (Pallas kernel on TPU, a
    gather + the contiguous reference path elsewhere), then the new
    token's K/V is written through the table."""
    b = x.shape[0]
    q = _project_q(cfg, params, x)
    k1, v1 = _project_kv(cfg, params, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k1 = apply_rope(k1, pos[:, None], cfg.rope_theta)
    from repro.kernels import ops as kops
    with jax.named_scope("attention"):
        out = kops.decode_attention_paged(
            q[:, 0], cache["k"], cache["v"], cache["pos"], bt,
            k1[:, 0], v1[:, 0], pos, softcap=cfg.attn_softcap)
    out = out.reshape(b, 1, -1) @ params["wo"]
    with jax.named_scope("kv_write"):
        new_cache = paged_write_token(cache, bt, k1, v1, pos)
    return out, new_cache


def attn_decode(cfg: ModelConfig, params, x, cache, pos, *, window: int = 0):
    """Single-token decode. x: [B,1,D]; pos: [B] absolute position of x.

    Attends over the cache plus the current token, then writes the token
    into the cache. Returns (out [B,1,D], new_cache).
    """
    b = x.shape[0]
    q = _project_q(cfg, params, x)                     # [B,1,H,Dh]
    k1, v1 = _project_kv(cfg, params, x)               # [B,1,Hkv,Dh]
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k1 = apply_rope(k1, pos[:, None], cfg.rope_theta)

    from repro.kernels import ops as kops
    with jax.named_scope("attention"):
        out = kops.decode_attention(
            q[:, 0], cache["k"], cache["v"], cache["pos"],
            k1[:, 0], v1[:, 0], pos,
            window=window, softcap=cfg.attn_softcap)
    out = out.reshape(b, 1, -1) @ params["wo"]
    with jax.named_scope("kv_write"):
        new_cache = cache_write_token(cache, k1, v1, pos, window=window)
    return out, new_cache


def attn_cross(cfg: ModelConfig, params, x, cross_kv):
    """Cross-attention (whisper decoder): full attention over encoder K/V."""
    b, s, _ = x.shape
    q = _project_q(cfg, params, x)
    k, v = cross_kv["k"], cross_kv["v"]
    sk = k.shape[1]
    q_pos = jnp.zeros((b, s), jnp.int32)
    k_pos = jnp.zeros((b, sk), jnp.int32)
    out = blockwise_attention(q, k, v, q_pos, k_pos, causal=False,
                              softcap=cfg.attn_softcap)
    return out.reshape(b, s, -1) @ params["wo"]


def cross_kv_init(cfg: ModelConfig, params, enc_out):
    """Precompute decoder cross-attention K/V from encoder output."""
    k, v = _project_kv(cfg, params, enc_out)
    return {"k": k, "v": v}
