"""Dropless expert routing on the served path (``launch/serve.py``
``build_server``) and the expert-row counters.

``build_server`` sets each MoE layer's capacity factor to experts / top-k:
4.0 for Mixtral-8x7B (8 / 2, its capacities as before), 16.0 for
Qwen3-30B-A3B (128 / 8). A slot's capacity is then the call's token count.
Per engine call the telemetry counts ``moe.routed_rows`` ((token, expert)
pairs dispatched, over layers) and ``moe.computed_rows`` (capacity rows of
every slot a token reached, over layers: what ``moe_gemm`` computes), as
registry counters and as args of the ``decode.device`` / ``chunk.device``
spans."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.configs import get_config
from repro.launch.serve import build_server, dropless_capacity_factor
from repro.serving.api import RequestSpec
from repro.serving.engine import EngineConfig


def moe_cfg(experts: int, top_k: int, qk_norm: bool = False):
    cfg = reduced("mixtral_8x7b")
    return dataclasses.replace(cfg, qk_norm=qk_norm, moe=dataclasses.replace(
        cfg.moe, num_experts=experts, top_k=top_k))


@pytest.mark.parametrize("experts,top_k,factor", [
    (8, 2, 4.0),       # Mixtral-8x7B
    (128, 8, 16.0),    # Qwen3-30B-A3B
    (4, 2, 2.0)])      # the reduced preset the CPU tests serve
def test_build_server_serves_dropless(experts, top_k, factor):
    eng, _ = build_server(moe_cfg(experts, top_k, qk_norm=experts == 128),
                          EngineConfig(max_batch=4, max_seq=48, num_aw=2,
                                       num_ew=2, chunk_token_budget=16))
    moe = eng.cfg.moe
    assert moe.capacity_factor == factor == dropless_capacity_factor(moe)
    # each slot holds every token of a call: decode rows, chunk tokens
    assert round(factor * top_k * 4 / experts) == 4
    assert eng.prefill_capacity(16) == 16
    assert eng.api.placement.num_slots == 2 * experts


def test_the_published_mixtral_keeps_its_factor():
    assert dropless_capacity_factor(get_config("mixtral_8x7b").moe) == 4.0


@pytest.mark.parametrize("seg_len", [1, 4])
def test_expert_row_counters_match_a_hand_count(seg_len):
    """Every token picks all 4 experts (top-k = E, factor 1): a call that
    routes ``real`` tokens over T rows dispatches L x 4 x real pairs, and
    the kernel computes the capacity rows (T for decode, real rounded up
    to a power of two for a chunk) of the 4 primary slots in each of L
    layers; the shadow slots get nothing."""
    eng, _ = build_server(moe_cfg(4, 4), EngineConfig(
        max_batch=4, max_seq=96, num_aw=2, num_ew=2, chunk_token_budget=16,
        decode_segment_len=seg_len))
    layers, b = eng.cfg.num_layers, eng.ecfg.max_batch
    want = []

    def counted(fn, kind):
        def call(*args, **kw):
            real = int((np.asarray(args[2]) >= 0).sum())
            rows = b if kind == "decode" else \
                eng.prefill_capacity(real)
            want.append((kind, layers * 4 * real, layers * 4 * rows))
            return fn(*args, **kw)
        return call

    eng._prefill_chunk = counted(eng._prefill_chunk, "chunk")
    if seg_len == 1:
        eng._decode = counted(eng._decode, "decode")
    for i in range(3):
        eng.client.submit(RequestSpec(
            rid=f"r{i}", prompt=np.arange(1, 20 + 3 * i, dtype=np.int32),
            max_new=6))
    for _ in range(12):
        eng.step()
    spans = [sp for sp in eng.telemetry.tracer.spans
             if sp.name in ("decode.device", "chunk.device")]
    assert {sp.name for sp in spans} == {"decode.device", "chunk.device"}
    got = [(sp.name.split(".")[0], sp.args["routed_rows"],
            sp.args["computed_rows"]) for sp in spans]
    if seg_len == 1:
        assert got == want
    else:
        assert [g for g in got if g[0] == "chunk"] == want
        segments = [g for g in got if g[0] == "decode"]
        assert segments
        for _, routed, computed in segments:
            # up to seg_len steps: each with a live row routes its live
            # rows to all 4 experts and computes b rows of each
            steps = computed // (layers * 4 * b)
            assert computed == steps * layers * 4 * b
            assert 1 <= steps <= seg_len
            assert routed % (layers * 4) == 0 and \
                steps * layers * 4 <= routed <= steps * layers * 4 * b
    c = eng.telemetry.registry.counters
    assert c["moe.routed_rows"] == sum(g[1] for g in got) > 0
    assert c["moe.computed_rows"] == sum(g[2] for g in got)


def test_the_counters_add_no_device_sync():
    """The row counts read the dispatch-load drain the placement manager
    already makes: a decode step still drains twice (load, tokens) plus
    its checkpoint gather, as without the counters."""
    eng, _ = build_server(moe_cfg(4, 2), EngineConfig(
        max_batch=4, max_seq=48, num_aw=2, num_ew=2))
    for i in range(2):
        eng.client.submit(RequestSpec(
            rid=f"r{i}", prompt=np.arange(1, 9, dtype=np.int32) + i,
            max_new=20))
    eng.step()
    eng.step()
    c = eng.telemetry.registry.counters
    for _ in range(3):
        before, rows = c["step.device_syncs"], c["moe.routed_rows"]
        eng.step()
        assert c["step.device_syncs"] - before == 3
        assert c["moe.routed_rows"] > rows


def test_the_load_is_per_layer():
    eng, _ = build_server(moe_cfg(4, 2), EngineConfig(
        max_batch=4, max_seq=48, num_aw=2, num_ew=2))
    pos = jnp.asarray([0, -1, 3, -1], jnp.int32)
    _, _, load = eng._decode(eng.params, jnp.zeros((4,), jnp.int32), pos,
                             eng.cache, eng.route_state, with_load=True)
    load = np.asarray(load)
    assert load.shape == (eng.cfg.num_layers, eng.api.placement.num_slots)
    # two active rows, top-2, in every layer
    assert (load.sum(-1) == 4).all()
