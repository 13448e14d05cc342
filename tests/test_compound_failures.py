"""Compound-failure tests: multiple workers dying inside each other's
recovery windows. The bar is the paper's end-to-end exactness claim applied
to *overlapping* failures — no lost requests, outputs bit-identical to the
failure-free run — which exercises the elastic placement plane's pinned
failover replicas (plan_reprotect's dead_ews contract) and the per-request
restoration path simultaneously. Since the typed request API the same bar
covers the *scheduling* substrate: cancellation landing inside a recovery
window, and mixed-SLO workloads whose preemptions overlap AW+EW failures."""
import dataclasses

import jax
import numpy as np

from conftest import reduced
from repro.core.orchestrator import Orchestrator
from repro.serving.api import RequestSpec
from repro.serving.engine import EngineConfig, InferenceEngine

PROMPT_A = np.arange(1, 9, dtype=np.int32)
PROMPT_B = np.arange(2, 10, dtype=np.int32)


def make_engine(num_ew=2, num_shadow=None, **kw):
    cfg = reduced("mixtral_8x7b", cap_factor=4.0)
    if num_shadow is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_shadow_slots=num_shadow))
    defaults = dict(max_batch=8, max_seq=48, num_aw=2, num_ew=num_ew)
    defaults.update(kw)
    return InferenceEngine(cfg, EngineConfig(**defaults),
                           jax.random.PRNGKey(7))


def _dual_protected_engine():
    """3-EW pool with a custom placement generation: every expert of EW0
    AND EW1 has a failover replica on EW2 (the default layout only protects
    one EW at a time). 4 experts over 3 EWs with 6 shadow slots -> EW2 owns
    exactly 4 slots (primary pads 4,5 + shadows 8,11)."""
    eng = make_engine(num_ew=3, num_shadow=6)
    mgr = eng.placement_mgr
    p = eng.api.placement
    assert p.primary_slots == 6 and p.num_slots == 12
    owner = p.slot_owner()
    ew2_slots = [s for s in range(p.num_slots) if owner[s] == 2]
    assert len(ew2_slots) == 4
    slot_expert = np.full((p.num_slots,), -1, np.int32)
    slot_expert[:4] = np.arange(4)                  # identity primaries
    for ex, s in enumerate(ew2_slots):              # all replicas on EW2
        slot_expert[s] = ex
    plan = mgr.adopt(slot_expert, reason="dual protect ew0+ew1")
    eng.install_plan(plan)
    cand = plan.candidates()
    assert all(cand[e, 1] >= 0 and owner[cand[e, 1]] == 2 for e in range(4))
    return eng


def test_ew_dies_while_other_ew_mid_provision():
    """EW0 fails; while its replacement is still provisioning (T_w), EW1
    fails too. With both EWs' experts replica-covered on EW2, every token
    matches the failure-free run and nothing is lost."""
    ref_a = _dual_protected_engine().generate("a", PROMPT_A, 16)
    ref_b = _dual_protected_engine().generate("b", PROMPT_B, 16)

    eng = _dual_protected_engine()
    orch = Orchestrator(eng, worker_init_time=1.0, weight_push_time=0.2)
    eng.submit("a", PROMPT_A, 16)
    eng.submit("b", PROMPT_B, 16)
    for _ in range(4):
        eng.step()
    orch.inject_failure("ew", 0, now=10.0)
    fired = orch.tick(10.0 + orch.detection_latency() + 1e-6)
    assert any(e.kind == "detected" for e in fired)
    assert eng.failed_ews == {0}
    for _ in range(3):
        eng.step()
    # EW0's replacement is mid-provision (ready ~11.03+T_w) when EW1 dies
    orch.inject_failure("ew", 1, now=10.5)
    fired = orch.tick(10.5 + orch.detection_latency() + 1e-6)
    assert any(e.kind == "detected" for e in fired)
    assert eng.failed_ews == {0, 1}
    while eng.active_requests():
        eng.step()
    assert eng.requests["a"].tokens == ref_a
    assert eng.requests["b"].tokens == ref_b
    # both replacements eventually provision; re-pointing while EW1 was
    # still down must have pinned its failover replicas (dead_ews contract)
    orch.tick(11.2)
    assert eng.failed_ews == {1}
    from repro.core import selfheal
    assert selfheal.experts_without_healthy_replica(
        eng.route_state, eng.api.placement).size == 0
    orch.tick(11.8)
    assert eng.failed_ews == set()
    assert orch.outstanding == 0


def test_aw_and_ew_die_in_same_detection_window():
    """AW0 and EW0 fail inside one detection window: per-request restoration
    (checkpointed KV onto AW1) composes with the shadow failover (EW0's
    experts re-pointed to replicas) — bit-identical, nothing lost."""
    ref_a = make_engine().generate("a", PROMPT_A, 14)
    ref_b = make_engine().generate("b", PROMPT_B, 14)

    eng = make_engine()
    orch = Orchestrator(eng, worker_init_time=1.0)
    eng.submit("a", PROMPT_A, 14)     # -> AW0 (least loaded, lowest id)
    eng.submit("b", PROMPT_B, 14)     # -> AW1
    for _ in range(4):
        eng.step()
    assert eng.requests["a"].aw == 0 and eng.requests["b"].aw == 1
    orch.inject_failure("aw", 0, now=5.0)
    orch.inject_failure("ew", 0, now=5.0)
    fired = orch.tick(5.0 + orch.detection_latency() + 1e-6)
    assert sorted(e.kind for e in fired) == ["detected", "detected"]
    assert eng.failed_aws == {0} and eng.failed_ews == {0}
    assert eng.requests["a"].aw == 1          # restored onto the healthy AW
    while eng.active_requests():
        eng.step()
    assert eng.requests["a"].tokens == ref_a
    assert eng.requests["b"].tokens == ref_b
    assert eng.store.stats.restores == 1
    # background provisioning restores the full pool
    orch.tick(7.0)
    assert eng.failed_aws == set() and eng.failed_ews == set()
    assert orch.outstanding == 0


def test_aw_and_ew_lost_mid_decode_dropless_at_e_over_k_16():
    """Qwen3-30B-A3B's ratio of experts to choices (E/k = 16; here 32
    experts, top-2) through ``build_server``, which serves it dropless:
    AW0 and EW0 lost mid-decode, with both AWs decoding, leave every
    request's tokens as the failure-free run's."""
    from repro.launch.serve import build_server
    cfg = reduced("mixtral_8x7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=32, top_k=2))
    prompts = {f"r{i}": np.arange(1 + i, 9 + 2 * i, dtype=np.int32)
               for i in range(4)}

    def served(fail: bool):
        eng, orch = build_server(cfg, EngineConfig(
            max_batch=8, max_seq=48, num_aw=2, num_ew=2,
            chunk_token_budget=16))
        assert eng.cfg.moe.capacity_factor == 16.0
        for rid, p in prompts.items():
            eng.client.submit(RequestSpec(rid=rid, prompt=p, max_new=14))
        for _ in range(6):
            eng.step()
        if fail:
            assert {eng.requests[r].aw for r in prompts} == {0, 1}
            assert all(eng.requests[r].tokens for r in prompts)
            orch.inject_failure("aw", 0, now=5.0)
            orch.inject_failure("ew", 0, now=5.0)
            orch.tick(5.0 + orch.detection_latency() + 1e-6)
            assert eng.failed_aws == {0} and eng.failed_ews == {0}
        while eng.active_requests():
            eng.step()
        return {r: eng.requests[r].tokens for r in prompts}

    assert served(True) == served(False)


def test_compound_failure_during_chunked_prefill():
    """AW dies mid-chunked-prefill AND an EW dies in the same window: the
    prefill stream resumes from its committed cursor on the healthy AW
    while expert traffic rides the shadows — the finished output equals the
    failure-free run's."""
    long_prompt = np.arange(1, 33, dtype=np.int32)
    kw = dict(chunk_token_budget=8, prefill_bucket=16, max_seq=64)
    ref = make_engine(**kw).generate("r", long_prompt, 10)

    eng = make_engine(**kw)
    orch = Orchestrator(eng, worker_init_time=1.0)
    eng.submit("r", long_prompt, 10)
    eng.step()                                  # a budgeted chunk lands
    r = eng.requests["r"]
    assert r.prefilling and r.prefill_cursor > 0
    aw = r.aw
    orch.inject_failure("aw", aw, now=3.0)
    orch.inject_failure("ew", 0, now=3.0)
    orch.tick(3.0 + orch.detection_latency() + 1e-6)
    while not eng.requests["r"].done:
        eng.step()
    assert eng.requests["r"].tokens == ref
    assert eng.chunked.stats.resumed == 1       # stream resumed, not redone


def test_cancel_during_aw_recovery_loses_no_other_request():
    """AW0 dies holding two requests; one of them is cancelled inside the
    recovery window (restored-or-still-queued). The cancellation must tear
    down cleanly — no stale recovery entry, no slot or store leak — and
    every surviving request must still finish bit-identical."""
    ref_b = make_engine(max_batch=4).generate("b", PROMPT_B, 14)
    ref_c = make_engine(max_batch=4).generate("c", PROMPT_A + 1, 14)

    eng = make_engine(max_batch=4)        # 2 slots per AW
    orch = Orchestrator(eng, worker_init_time=1.0)
    # least_loaded: a -> AW0, b -> AW1, c -> AW0 (tie toward lowest id)
    ha = eng.client.submit(RequestSpec(rid="a", prompt=PROMPT_A,
                                       max_new=14))
    hb = eng.client.submit(RequestSpec(rid="b", prompt=PROMPT_B,
                                       max_new=14))
    hc = eng.client.submit(RequestSpec(rid="c", prompt=PROMPT_A + 1,
                                       max_new=14))
    assert eng.requests["a"].aw == 0 and eng.requests["c"].aw == 0
    assert eng.requests["b"].aw == 1
    for _ in range(4):
        eng.step()
    orch.inject_failure("aw", 0, now=5.0)
    orch.tick(5.0 + orch.detection_latency() + 1e-6)
    # AW1 had one free slot: one victim restored, the other still queued
    assert eng.gateway.depth() == 1
    # cancel "a" inside the recovery window, whichever side it landed on
    assert ha.cancel(now=5.1)
    assert ha.state() == "cancelled"
    assert eng.gateway.find("a") is None      # no stale recovery entry
    assert "a" not in eng.requests
    while not (hb.done() and hc.done()):
        eng.step()
    assert hb.tokens() == ref_b
    assert hc.tokens() == ref_c               # the other victim lost nothing
    # background provisioning restores the full pool; slot accounting is
    # clean once the survivors release
    orch.tick(7.0)
    eng.release_request("b")
    eng.release_request("c")
    assert sum(w.slots.free_count() for w in eng.aws) == 4
    assert not eng.store.active_requests_on(0)


def test_mixed_class_workload_with_preemption_under_aw_ew_failure():
    """The full stack at once: a batch wave saturates the pool, an
    interactive arrival preempts a victim, then an AW and an EW die in the
    same detection window. Every request — preempted, restored, rerouted —
    finishes bit-identical to its failure-free run."""
    prompts = {f"b{i}": PROMPT_A + i for i in range(4)}
    prompts["int"] = PROMPT_B
    refs = {rid: make_engine(max_batch=4).generate(rid, p, 18)
            for rid, p in prompts.items()}

    eng = make_engine(max_batch=4)
    orch = Orchestrator(eng, worker_init_time=1.0)
    handles = {rid: eng.client.submit(RequestSpec(
        rid=rid, prompt=prompts[rid], max_new=18, slo_class="batch"))
        for rid in ("b0", "b1", "b2", "b3")}
    for _ in range(3):
        eng.step()
    handles["int"] = eng.client.submit(RequestSpec(
        rid="int", prompt=prompts["int"], max_new=18,
        slo_class="interactive"), now=4.0)
    assert eng.gateway.stats.preemptions == 1   # a victim was evicted
    orch.inject_failure("aw", 0, now=5.0)
    orch.inject_failure("ew", 0, now=5.0)
    orch.tick(5.0 + orch.detection_latency() + 1e-6)
    n = 0
    while not all(h.done() for h in handles.values()) and n < 600:
        eng.step()
        orch.tick(6.0 + 0.01 * n)
        for rid in [r.rid for r in eng.requests.values() if r.done]:
            eng.release_request(rid)
        n += 1
    for rid, ref in refs.items():
        assert handles[rid].tokens() == ref, rid
    # preempted/cancelled/deadline events rode the orchestrator timeline
    assert any(e.kind == "preempted" for e in orch.events)
    assert eng.store.stats.restores >= 2        # preemption + AW recovery
