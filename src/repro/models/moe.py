"""Sparse MoE layer wired through the Tarragon REFE datapath.

Covers the assigned MoE architectures:
  * qwen2-moe-a2.7b — 60 routed top-4 + 4 shared experts
  * kimi-k2-1t-a32b — 384 routed top-8 + 1 shared expert
and the paper's own Mixtral-8x7B (8 routed top-2).

Two routing modes:
  * tarragon=True  — ERT/slot-space routing with shadow slots and health
    masks (the paper's system).
  * tarragon=False — static expert->EW binding (MegaScale-Infer baseline):
    no shadow slots, no ERT indirection.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import ert as ert_lib
from repro.core import refe
from repro.core import shadow as shadow_lib
from repro.kernels import ops as kops
from repro.models.layers import dense_init, mlp, mlp_init


def moe_placement(cfg: ModelConfig, num_ew: int,
                  tarragon: bool = True) -> ert_lib.ExpertPlacement:
    n_shadow = cfg.moe.num_shadow_slots if tarragon else 0
    return ert_lib.default_placement(cfg.moe.num_experts, num_ew, n_shadow)


def moe_init(key, cfg: ModelConfig, placement: ert_lib.ExpertPlacement):
    """One MoE layer's params.

    The stored bank holds one row per *logical* expert, padded to
    ``placement.primary_slots`` (a multiple of num_ew) so the expert axis
    always divides the EW mesh axis — e.g. Qwen's 60 experts are stored as
    64 rows on 16 EWs. The physical slot bank (primaries, shadows, and any
    replicas a placement plan creates) is gathered from these rows through
    ``RouteState.slot_expert`` at apply time, so there is no separate
    shadow bank to keep in sync with the placement."""
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.moe.d_ff
    e_store = placement.primary_slots
    ks = jax.random.split(key, 5)
    std = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    experts = {
        "wg": jax.random.normal(ks[0], (e_store, d, f), jnp.float32) * std,
        "wu": jax.random.normal(ks[1], (e_store, d, f), jnp.float32) * std,
        "wd": jax.random.normal(ks[2], (e_store, f, d), jnp.float32) *
        (1.0 / jnp.sqrt(jnp.asarray(f, jnp.float32))),
    }
    p = {"router": dense_init(ks[3], d, e), "experts": experts}
    if cfg.moe.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.moe.shared_d_ff, gated=True)
    return p


def moe_apply(cfg: ModelConfig, params, x, route_state: refe.RouteState,
              placement: ert_lib.ExpertPlacement,
              capacity: Optional[int] = None, token_mask=None):
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar, slot_load [P]).

    The flattened [T, D] token batch is what flows over the AW->EW datapath;
    B is data-parallel over AWs, the slot dim over EWs. ``token_mask``
    ([B, S] bool, optional) flags real tokens; pads are excluded from
    expert-capacity competition (pad-free dispatch). ``slot_load`` is the
    device-side dispatch counter the placement manager's EMA drains.
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("router"):
        logits = xt @ params["router"].astype(xt.dtype)
        routing = refe.route(
            xt, logits, route_state, placement,
            top_k=cfg.moe.top_k, capacity_factor=cfg.moe.capacity_factor,
            capacity=capacity, batch=b,
            token_mask=None if token_mask is None
            else token_mask.reshape(b * s))

    # physical slot bank, gathered through the plan's slot indirection: any
    # slot (primary, shadow, replica) serves its resident expert's rows —
    # a placement change re-points this without touching the trace
    with jax.named_scope("slot_bank_gather"):
        bank = shadow_lib.resident_slot_bank(params["experts"],
                                             route_state.slot_expert)

    # the kernel skips the slots no token reached (idle shadows, cold
    # experts): their rows of expert_in are zero, and so is their output
    counts = routing["slot_load"].astype(jnp.int32)

    def expert_fn(expert_in):
        with jax.named_scope("expert_ffn"):
            return kops.expert_ffn(expert_in, bank["wg"].astype(x.dtype),
                                   bank["wu"].astype(x.dtype),
                                   bank["wd"].astype(x.dtype), act=cfg.act,
                                   counts=counts)

    y = refe.expert_io(xt, routing, expert_fn)

    if "shared" in params:
        y = y + mlp(params["shared"], xt, cfg.act)

    return y.reshape(b, s, d), routing["aux_loss"], routing["slot_load"]
