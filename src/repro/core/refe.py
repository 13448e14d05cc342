"""Reconfigurable Forwarding Engine (REFE): the AW<->EW datapath.

Paper §4: each AW dispatches token embeddings to EWs through the REFE, which
resolves logical expert ids via the ERT and routes over point-to-point RDMA.
JAX/TPU adaptation: the dispatch/combine is expressed as capacity-based
one-hot contractions over the *physical slot space* (see core/ert.py). With
tokens sharded over the ``data`` axis (= AW shards) and slots sharded over the
``model`` axis (= EW shards), XLA lowers the two contractions into exactly the
asymmetric M2N scatter/gather the paper describes — and because the routing
tables/health masks are runtime arrays, a failover changes *where tokens
flow* without touching the compiled program.

Self-healing semantics carried in-band (paper §5):
  * AW-side (EW failure): ``resolve_active_slots`` never routes to a slot on
    a dead EW — tokens flow to the shadow/alternate slot in the same step
    ("immediate reroute + replay at the frontier").
  * EW-side (AW failure): tokens owned by dead AWs are masked out of the
    dispatch (gate weights zeroed) — expert batches proceed with the healthy
    subset instead of waiting ("sufficient subset" batching).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import ert as ert_lib


class RouteState(NamedTuple):
    """Runtime routing state threaded through the jitted step (all data,
    never compile-time constants).

    The slot-indirection pair (``slot_expert``, ``slot_owner``) is what makes
    the expert plane *elastic*: the expert bank is gathered through
    ``slot_expert`` and health is resolved through ``slot_owner``, so a
    placement change — rebalance, EW scale-out/in, shadow promotion — is a
    pure array update installed between steps, never a new jit trace."""

    candidates: jax.Array      # [E, R] int32 — ERT (priority order per expert)
    ew_health: jax.Array       # [max_ew] bool
    aw_health: jax.Array       # [num_aw] bool
    slot_expert: jax.Array     # [P] int32 — resident logical expert per slot
    #                            (-1 = empty slot; bank rows gather through it)
    slot_owner: jax.Array      # [P] int32 — EW owning each slot (-1 = parked)
    split_slot: jax.Array      # [E] int32 — load-bearing replica slot for
    #                            traffic splitting (-1 = no split); only used
    #                            while its owner is healthy

    @staticmethod
    def healthy(placement: ert_lib.ExpertPlacement, num_aw: int,
                shadow_assignment=None, num_ew: int = 0) -> "RouteState":
        """The static identity layout (primary slot e = expert e, shadows per
        ``shadow_assignment``). ``num_ew`` oversizes the EW-health axis for
        elastic pools (spare EW ids start unhealthy); 0 = exactly the
        placement's EW count."""
        if shadow_assignment is None:
            shadow_assignment = ert_lib.initial_shadow_assignment(placement)
        # host-side numpy: must stay concrete even under eval_shape tracing
        import numpy as np
        shadow_assignment = np.asarray(shadow_assignment)
        cand = ert_lib.build_candidates(placement, shadow_assignment)
        max_ew = max(num_ew, placement.num_ew)
        health = np.zeros((max_ew,), bool)
        health[: placement.num_ew] = True
        return RouteState(
            candidates=jnp.asarray(cand, jnp.int32),
            ew_health=jnp.asarray(health),
            aw_health=jnp.ones((num_aw,), bool),
            slot_expert=jnp.asarray(
                ert_lib.initial_slot_expert(placement, shadow_assignment),
                jnp.int32),
            slot_owner=jnp.asarray(placement.slot_owner(), jnp.int32),
            split_slot=jnp.full((placement.num_experts,), -1, jnp.int32),
        )


def token_aw_owner(num_tokens: int, num_aw: int, batch: int = 0):
    """AW shard owning each token (tokens are batch-major; batch rows are
    data-parallel over AWs, so ownership is contiguous row blocks)."""
    batch = batch or num_tokens
    seq = max(1, num_tokens // batch)
    row = jnp.arange(num_tokens) // seq
    return jnp.minimum(row * num_aw // batch, num_aw - 1)


# Above this token count the flat one-hot dispatch ([T, P, C] — cost
# O(T*P*C*D), catastrophic at 1M train tokens) switches to GShard-style
# GROUPED dispatch: tokens split into groups of GROUP_SIZE with per-group
# capacity, so the one-hot is [G, S_g, P, C_g] (S_g-bounded) and the
# dispatch einsum costs O(T * S_g * k * cf * D / 1) per token — ~20% of
# expert FLOPs at S_g=512 instead of ~30x. Groups ride the data axis; the
# expert dim rides the model axis, so expert compute is fully 2D-sharded
# with a single psum-combine per layer. See EXPERIMENTS.md §Perf iter 1.
ONEHOT_MAX_TOKENS = 2048
GROUP_SIZE = 512


def intra_slot_positions(slot_idx, valid, num_slots: int):
    """Rank of each (token, choice) within its target slot (order = flat
    (t, k) arrival order — the EW-side layer-wise batch fill order)."""
    t, k = slot_idx.shape
    flat_slot = slot_idx.reshape(t * k)
    flat_valid = valid.reshape(t * k)
    oh = jax.nn.one_hot(flat_slot, num_slots, dtype=jnp.int32)
    oh = oh * flat_valid.astype(jnp.int32)[:, None]
    pos = (jnp.cumsum(oh, axis=0) - oh)
    pos = jnp.take_along_axis(pos, flat_slot[:, None], axis=1)[:, 0]
    return pos.reshape(t, k)


def route(x, router_logits, route_state: RouteState,
          placement: ert_lib.ExpertPlacement, *, top_k: int,
          capacity_factor: float, capacity: Optional[int] = None,
          batch: int = 0, token_mask=None):
    """Full REFE routing decision for a flat token batch.

    x: [T, D]; router_logits: [T, E]. Returns routing metadata (slot ids,
    intra-slot positions, gate weights, aux loss); ``expert_io`` turns it
    into the AW->EW datapath.

    ``token_mask`` ([T] bool, optional) marks which tokens are real work:
    pad tokens (prefill length/row padding, inactive chunk rows) get
    ``False`` and are excluded from intra-slot ranking, so they never
    compete with real tokens for per-expert capacity cells.
    """
    t, e = router_logits.shape
    slot_owner = route_state.slot_owner      # [P] data, never a trace const

    active_slot, expert_alive = ert_lib.resolve_active_slots(
        route_state.candidates, route_state.ew_health, slot_owner)

    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    # dead experts (no healthy replica anywhere) are masked from selection
    probs = probs * expert_alive[None, :]
    gate_w, topk_idx = jax.lax.top_k(probs, top_k)           # [T, K]
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)

    slot_idx = active_slot[topk_idx]                          # [T, K]

    # load-bearing replicas (placement-manager decision): tokens of a split
    # expert alternate between its active slot and the replica slot by
    # (token, choice) parity — half the dispatch load moves off the primary
    # EW while the replica's owner stays healthy. Weights are identical, so
    # a kept token computes the same value either way; outputs are
    # bit-identical whenever capacity does not bind (splitting also doubles
    # the expert's effective capacity, so under a *tight* capacity factor
    # the kept-token set can only grow, which changes which drops occur).
    split = route_state.split_slot[topk_idx]                  # [T, K]
    sp_owner = slot_owner[jnp.maximum(split, 0)]
    sp_ok = (split >= 0) & (sp_owner >= 0) & \
        route_state.ew_health[jnp.maximum(sp_owner, 0)]
    parity = (jnp.arange(t)[:, None] + jnp.arange(top_k)[None, :]) % 2
    slot_idx = jnp.where(sp_ok & (parity == 1),
                         jnp.maximum(split, 0), slot_idx)

    # EW-side self-healing: drop tokens from failed AWs; pad-free dispatch:
    # drop pad tokens before they claim capacity ranks
    owner = token_aw_owner(t, route_state.aw_health.shape[0], batch=batch)
    token_valid = route_state.aw_health[owner]
    if token_mask is not None:
        token_valid = token_valid & token_mask

    grouped = t > ONEHOT_MAX_TOKENS
    if grouped:
        s_g = GROUP_SIZE
        while t % s_g:
            s_g //= 2
        g = t // s_g
    else:
        g, s_g = 1, t
    if capacity is None:
        capacity = int(max(1, round(capacity_factor * top_k * s_g / e)))

    valid = token_valid[:, None] & (gate_w > 0)
    # intra-slot rank per GROUP (per-group capacity)
    pos = jax.vmap(
        lambda si, va: intra_slot_positions(si, va, placement.num_slots)
    )(slot_idx.reshape(g, s_g, top_k), valid.reshape(g, s_g, top_k))
    pos = pos.reshape(t, top_k)
    keep = valid & (pos < capacity)

    # load-balance auxiliary loss (Switch-style), over logical experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(topk_idx, e, dtype=jnp.float32), axis=1),
        axis=0) / top_k
    aux_loss = e * jnp.sum(me * ce)

    # per-slot dispatch load counter (tokens actually dispatched, after
    # health masks / capacity drops / replica splitting): a summed one-hot
    # collected device-side, drained into the ExpertPlacementManager's EMA
    # on the host — the telemetry behind load-aware rebalancing.
    slot_load = jnp.zeros((placement.num_slots,), jnp.float32).at[
        slot_idx.reshape(-1)].add(keep.reshape(-1).astype(jnp.float32))

    return {
        "capacity": capacity,
        "num_slots": placement.num_slots,
        "active_slot": active_slot,    # [E]
        "expert_alive": expert_alive,  # [E]
        "token_valid": token_valid,    # [T]
        "slot_idx": slot_idx,          # [T, K]
        "pos": pos,                    # [T, K]
        "keep": keep,                  # [T, K]
        "topk_idx": topk_idx,
        "gate_w": gate_w,
        "aux_loss": aux_loss,
        "slot_load": slot_load,        # [P] dispatched-token count per slot
        "grouped": grouped,
        "groups": g,
        "group_size": s_g,
    }


def routing_onehots(routing):
    """[T, P, C] dispatch/combine one-hots (small-T / test path)."""
    p, c = routing["num_slots"], routing["capacity"]
    slot_oh = jax.nn.one_hot(routing["slot_idx"], p, dtype=jnp.float32)
    slot_oh = slot_oh * routing["keep"].astype(jnp.float32)[..., None]
    pos_oh = jax.nn.one_hot(routing["pos"], c, dtype=jnp.float32)
    dispatch = jnp.einsum("tkp,tkc->tpc", slot_oh, pos_oh)
    combine = jnp.einsum("tkp,tkc->tpc",
                         slot_oh * routing["gate_w"][..., None], pos_oh)
    return dispatch, combine


def expert_io(x, routing, expert_fn):
    """The paper's ``expert_io(expert_id, layer_id, token_embeddings)`` API:
    scatter token embeddings to expert slots, run expert compute, gather.

    x: [T, D]; expert_fn: [P, ..., D] -> [P, ..., D] (ellipsis dims carried
    through the per-slot FFN). Returns y [T, D]. The dispatch/combine
    contractions are the M2N datapath (AW->EW and EW->AW hops).
    """
    t, d = x.shape
    p, c = routing["num_slots"], routing["capacity"]
    if not routing["grouped"]:
        with jax.named_scope("dispatch"):
            dispatch, combine = routing_onehots(routing)
            expert_in = jnp.einsum("tpc,td->pcd", dispatch.astype(x.dtype),
                                   x)
        expert_out = expert_fn(expert_in)
        with jax.named_scope("combine"):
            return jnp.einsum("tpc,pcd->td",
                              combine.astype(expert_out.dtype), expert_out)

    # GShard-style grouped dispatch: groups ride the data axis, slots the
    # model axis -> expert compute is 2D-sharded, combine psums over slots.
    with jax.named_scope("dispatch"):
        g, s_g = routing["groups"], routing["group_size"]
        k = routing["slot_idx"].shape[1]
        slot_oh = jax.nn.one_hot(
            routing["slot_idx"].reshape(g, s_g, k), p, dtype=x.dtype)
        slot_oh = slot_oh * routing["keep"].reshape(
            g, s_g, k, 1).astype(x.dtype)
        pos_oh = jax.nn.one_hot(
            routing["pos"].reshape(g, s_g, k), c, dtype=x.dtype)
        dispatch = jnp.einsum("gskp,gskc->gspc", slot_oh, pos_oh)
        combine = jnp.einsum(
            "gskp,gskc->gspc",
            slot_oh * routing["gate_w"].reshape(g, s_g, k, 1).astype(x.dtype),
            pos_oh)
        xg = x.reshape(g, s_g, d)
        expert_in = jnp.einsum("gspc,gsd->pgcd", dispatch, xg)   # [P,G,C,D]
    expert_out = expert_fn(expert_in)
    with jax.named_scope("combine"):
        y = jnp.einsum("gspc,pgcd->gsd", combine, expert_out)
        return y.reshape(t, d)
