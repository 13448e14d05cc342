"""Generic decoder stack builder covering the dense / MoE / VLM families
(qwen2, qwen2-moe, h2o-danube, chameleon, gemma2, granite, kimi-k2, mixtral).

Layers are stacked with ``lax.scan`` over repeated *units* (one unit =
``len(cfg.attn_pattern)`` layers, e.g. gemma2's (local, global) pair) so HLO
size and compile time stay flat for 26-88 layer configs. MoE layers route
through the Tarragon REFE datapath (models/moe.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import ert as ert_lib
from repro.core import refe
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (cast_tree, embed_init, mlp, mlp_init,
                                 rmsnorm, rmsnorm_init, unembed)


class ModelApi(NamedTuple):
    cfg: ModelConfig
    placement: Optional[ert_lib.ExpertPlacement]
    num_aw: int
    num_ew: int
    init_params: Callable[..., Any]
    init_cache: Callable[..., Any]
    forward_train: Callable[..., Any]   # (params, batch, rs) -> (logits, aux)
    prefill: Callable[..., Any]         # -> (last_logits, cache)
    decode: Callable[..., Any]          # -> (logits, cache)
    init_route_state: Callable[..., refe.RouteState]
    # chunked prefill: (params, tokens [B,C], positions [B,C], caches, rs)
    # -> caches. None for families without a resumable prefill path
    # (recurrent state / ring buffers / enc-dec).
    prefill_chunk: Optional[Callable[..., Any]] = None
    # True when prefill/decode/prefill_chunk accept a static ``with_load``
    # flag appending the accumulated per-slot dispatch-load counter [P] to
    # their returns (the placement manager's telemetry).
    reports_load: bool = False
    # True when ``decode`` may be scanned into multi-token device segments
    # (serving/decode_loop.py): requires a pure positional cache (pos -1
    # rows drop their writes) so a row finishing mid-segment is a no-op.
    # Recurrent-state families keep per-step dispatch.
    supports_decode_segments: bool = False


# --------------------------------------------------------------------------
# unit geometry
# --------------------------------------------------------------------------

def _unit_windows(cfg: ModelConfig):
    """Sliding window per unit position (0 = full attention)."""
    wins = []
    for kind in cfg.attn_pattern:
        if kind == "global":
            wins.append(0)
        elif kind == "local":
            wins.append(cfg.sliding_window)
        else:  # "layer"
            wins.append(cfg.sliding_window)
    return tuple(wins)


def _num_units(cfg: ModelConfig):
    u = len(cfg.attn_pattern)
    n_moe_first = cfg.moe.first_k_dense if cfg.moe.enabled else 0
    scan_layers = cfg.num_layers - n_moe_first
    assert scan_layers % u == 0, (
        f"{cfg.name}: {scan_layers} scanned layers not divisible by "
        f"pattern {cfg.attn_pattern}")
    return scan_layers // u


# --------------------------------------------------------------------------
# single layer (attn + ffn) init / apply
# --------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, use_moe: bool, placement):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": rmsnorm_init(cfg.d_model),
        "attn": attn.attn_init(ks[0], cfg),
        "ln2": rmsnorm_init(cfg.d_model),
    }
    if use_moe:
        p["moe"] = moe_mod.moe_init(ks[1], cfg, placement)
    else:
        d_ff = cfg.d_ff or cfg.moe.d_ff
        p["mlp"] = mlp_init(ks[1], cfg.d_model, d_ff, cfg.mlp_gated)
    return p


def _layer_apply(cfg: ModelConfig, p, x, *, window: int, mode: str,
                 positions=None, pos=None, cache=None, route_state=None,
                 placement=None, capacity=None, token_mask=None, bt=None):
    """mode: 'train' | 'prefill' | 'chunk' | 'decode'. ``bt`` is the
    [B, nblk] block table of a paged cache (None = contiguous layout);
    when set, ``cache`` holds physical page pools instead of per-slot
    rows and the paged attention twins are used."""
    with jax.named_scope("attn"):
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        if mode == "decode" and bt is not None:
            a, new_cache = attn.attn_decode_paged(cfg, p["attn"], h, cache,
                                                  bt, pos)
        elif mode == "decode":
            a, new_cache = attn.attn_decode(cfg, p["attn"], h, cache, pos,
                                            window=window)
        elif mode == "chunk" and bt is not None:
            a, new_cache = attn.attn_chunk_paged(cfg, p["attn"], h, cache,
                                                 bt, positions)
        elif mode == "chunk":
            a, new_cache = attn.attn_chunk(cfg, p["attn"], h, cache,
                                           positions, window=window)
        else:
            a, new_cache = attn.attn_full(cfg, p["attn"], h, positions,
                                          window=window, cache=cache)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    n_slots = placement.num_slots if placement is not None else 0
    load = jnp.zeros((n_slots,), jnp.float32)
    if "moe" in p:
        with jax.named_scope("moe"):
            f, aux, load = moe_mod.moe_apply(cfg, p["moe"], h, route_state,
                                             placement, capacity=capacity,
                                             token_mask=token_mask)
    else:
        f = mlp(p["mlp"], h, cfg.act)
    return x + f, new_cache, aux, load


# --------------------------------------------------------------------------
# builder
# --------------------------------------------------------------------------

def build_decoder(cfg: ModelConfig, *, num_aw: int = 1, num_ew: int = 1,
                  tarragon: bool = True) -> ModelApi:
    windows = _unit_windows(cfg)
    u = len(windows)
    r = _num_units(cfg)
    n_first = cfg.moe.first_k_dense if cfg.moe.enabled else 0
    placement = (moe_mod.moe_placement(cfg, num_ew, tarragon)
                 if cfg.moe.enabled else None)
    dtype = cfg.jnp_dtype

    # ---- init ------------------------------------------------------------
    def init_params(key):
        keys = jax.random.split(key, 3 + n_first)
        params = {
            "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(keys[1], cfg.vocab_size,
                                           cfg.d_model)
        for i in range(n_first):
            params[f"dense{i}"] = _layer_init(keys[2 + i], cfg, False,
                                              placement)
        unit_keys = jax.random.split(keys[-1], r)

        def unit_init(k):
            lk = jax.random.split(k, u)
            return tuple(
                _layer_init(lk[i], cfg, cfg.moe.enabled, placement)
                for i in range(u))

        params["blocks"] = jax.vmap(unit_init)(unit_keys)
        return cast_tree(params, dtype)

    # ---- caches ------------------------------------------------------------
    def init_cache(batch: int, max_seq: int):
        caches = {}
        for i in range(n_first):
            caches[f"dense{i}"] = attn.init_cache(cfg, batch, max_seq,
                                                  window=windows[0])

        def one(win):
            c = attn.init_cache(cfg, batch, max_seq, window=win)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (r,) + a.shape), c)

        caches["blocks"] = tuple(one(w) for w in windows)
        return caches

    # ---- forward ------------------------------------------------------------
    def _embed(params, tokens):
        return params["embed"].astype(dtype)[tokens]

    n_slots = placement.num_slots if placement is not None else 0

    def _run_stack(params, x, mode, positions=None, pos=None, caches=None,
                   route_state=None, capacity=None, token_mask=None):
        aux_total = jnp.zeros((), jnp.float32)
        load_total = jnp.zeros((n_slots,), jnp.float32)
        new_caches = {} if caches is not None else None
        # paged engines carry one block table at the top of the cache dict;
        # it is threaded to every attention layer and returned unchanged.
        # The branch is python-level: an engine is paged or contiguous for
        # life, so each jitted entry point still traces exactly once.
        bt = caches.get("bt") if caches is not None else None
        for i in range(n_first):
            c = caches[f"dense{i}"] if caches is not None else None
            x, nc, aux, load = _layer_apply(
                cfg, params[f"dense{i}"], x, window=windows[0], mode=mode,
                positions=positions, pos=pos, cache=c,
                route_state=route_state, placement=placement,
                capacity=capacity, token_mask=token_mask, bt=bt)
            aux_total += aux
            load_total += load
            if caches is not None:
                new_caches[f"dense{i}"] = nc

        def unit_body(carry, xs):
            h, auxc, loadc = carry
            unit_params, unit_caches = xs
            ncs = []
            for i in range(u):
                c = unit_caches[i] if unit_caches is not None else None
                h, nc, aux, load = _layer_apply(
                    cfg, unit_params[i], h, window=windows[i], mode=mode,
                    positions=positions, pos=pos, cache=c,
                    route_state=route_state, placement=placement,
                    capacity=capacity, token_mask=token_mask, bt=bt)
                auxc += aux
                loadc += load
                ncs.append(nc)
            ncs = tuple(ncs) if caches is not None else None
            return (h, auxc, loadc), ncs

        body = jax.checkpoint(unit_body) if cfg.remat else unit_body
        if caches is None:
            (x, aux_total, load_total), _ = jax.lax.scan(
                lambda c, p: body(c, (p, None)), (x, aux_total, load_total),
                params["blocks"])
        else:
            (x, aux_total, load_total), nb = jax.lax.scan(
                unit_body, (x, aux_total, load_total),
                (params["blocks"], caches["blocks"]))
            new_caches["blocks"] = nb
            if bt is not None:
                new_caches["bt"] = bt
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, aux_total, load_total

    def forward_train(params, batch, route_state):
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = _embed(params, tokens)
        x, _, aux, _ = _run_stack(params, x, "train", positions=positions,
                                  route_state=route_state)
        return unembed(cfg, params, x), aux

    def prefill(params, batch, route_state, max_seq: int, capacity=None,
                with_load: bool = False):
        """batch may carry a ``mask`` ([B, S] bool) flagging real tokens;
        pads then never compete for expert capacity (pad-free dispatch).
        ``with_load`` (static) appends the summed per-slot dispatch-load
        counter to the returns (placement-manager telemetry)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        caches = init_cache(b, max_seq)
        x = _embed(params, tokens)
        x, caches, _, load = _run_stack(
            params, x, "prefill", positions=positions, caches=caches,
            route_state=route_state, capacity=capacity,
            token_mask=batch.get("mask"))
        logits = unembed(cfg, params, x[:, -1])
        return (logits, caches, load) if with_load else (logits, caches)

    def prefill_chunk(params, tokens, positions, caches, route_state,
                      capacity=None, with_load: bool = False):
        """One budgeted prefill chunk over the shared slot-partitioned
        cache. tokens: [B, C] int32; positions: [B, C] absolute prompt
        positions (-1 = chunk padding or a row not in this chunk call —
        such rows, including live decode slots, are untouched). Returns
        the updated caches; logits are not needed mid-prompt (the first
        generated token rides the decode step, like the padded scheme)."""
        x = _embed(params, tokens)
        mask = positions >= 0
        x, caches, _, load = _run_stack(
            params, x, "chunk", positions=positions, caches=caches,
            route_state=route_state, capacity=capacity, token_mask=mask)
        return (caches, load) if with_load else caches

    def decode(params, tokens, pos, caches, route_state, capacity=None,
               with_load: bool = False):
        """tokens: [B] int32; pos: [B] absolute positions. Rows not decoding
        this step carry pos -1: they are masked out of expert-capacity
        competition (and out of the dispatch-load telemetry) exactly like
        prefill pads."""
        x = _embed(params, tokens[:, None])
        x, caches, _, load = _run_stack(params, x, "decode", pos=pos,
                                        caches=caches,
                                        route_state=route_state,
                                        capacity=capacity,
                                        token_mask=(pos >= 0)[:, None])
        logits = unembed(cfg, params, x[:, 0])
        return (logits, caches, load) if with_load else (logits, caches)

    def init_route_state():
        if placement is None:
            return refe.RouteState(
                candidates=jnp.zeros((0, 2), jnp.int32),
                ew_health=jnp.ones((num_ew,), bool),
                aw_health=jnp.ones((num_aw,), bool),
                slot_expert=jnp.zeros((0,), jnp.int32),
                slot_owner=jnp.zeros((0,), jnp.int32),
                split_slot=jnp.zeros((0,), jnp.int32))
        return refe.RouteState.healthy(placement, num_aw)

    return ModelApi(cfg, placement, num_aw, num_ew, init_params, init_cache,
                    forward_train, prefill, decode, init_route_state,
                    prefill_chunk=prefill_chunk, reports_load=True,
                    supports_decode_segments=True)
