"""Observability plane (serving/telemetry.py): streaming histograms vs
exact percentiles, the multi-consumer event bus, per-request span
completeness, stall attribution, exporter formats, the host spans and
counters around the engine's own work — and the two hard invariants
across a serving run that spans an AW failure, preemptions, a queued
cancel, and a prefix-warm chat turn: telemetry on/off is bit-identical,
and the plane mints zero new jit traces."""
import gc
import json
import math

import jax
import numpy as np
import pytest

from conftest import reduced
from repro.core.costmodel import TarragonProfile
from repro.core.events import timeline_from_bus
from repro.core.orchestrator import Orchestrator, WorkerEvent
from repro.data.workloads import make_workload
from repro.serving.api import RequestSpec
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.scheduler import FailurePlan, run_serving
from repro.serving.telemetry import (HOST, SCHEMA, STALL_CAUSES, EventBus,
                                     MetricsRegistry, StreamingHistogram,
                                     attribute_gap, live_planes, pct,
                                     summarize_latency)


# --------------------------------------------------------------------------
# percentile helpers
# --------------------------------------------------------------------------

def test_pct_empty_guard():
    assert pct([], 50) == 0.0
    assert pct(np.zeros((0,)), 99) == 0.0
    assert pct([3.0, 1.0, 2.0], 50) == 2.0


def test_summarize_latency():
    s = summarize_latency([])
    assert s["n"] == 0 and s["p99"] == 0.0
    s = summarize_latency([0.1] * 100)
    assert s["n"] == 100
    assert s["p50"] == pytest.approx(0.1)
    assert s["max"] == pytest.approx(0.1)


# --------------------------------------------------------------------------
# streaming histogram: O(1) memory, mergeable, bucket-bounded quantiles
# --------------------------------------------------------------------------

def exact_rank(vals: np.ndarray, q: float) -> float:
    """The order statistic the histogram's cumulative scan targets:
    smallest x with rank >= ceil(q * n)."""
    v = np.sort(np.asarray(vals))
    k = min(v.size - 1, max(0, math.ceil(q * v.size) - 1))
    return float(v[k])


def within_one_bucket(h: StreamingHistogram, streamed: float,
                      exact: float) -> bool:
    return abs(h.bucket_index(streamed) - h.bucket_index(exact)) <= 1


def test_histogram_quantiles_within_one_bucket():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.2, size=5000)
    h = StreamingHistogram()
    for v in vals:
        h.observe(v)
    assert h.count == vals.size
    for q in (0.50, 0.95, 0.99):
        assert within_one_bucket(h, h.quantile(q), exact_rank(vals, q)), \
            (q, h.quantile(q), exact_rank(vals, q))
    # streamed never escapes the observed range
    assert h.quantile(0.0) >= float(vals.min()) - 1e-12
    assert h.quantile(1.0) <= float(vals.max()) + 1e-12


def test_histogram_constant_memory():
    h = StreamingHistogram()
    n_buckets = h.counts.size
    for v in np.random.default_rng(1).exponential(size=10000):
        h.observe(v)
    assert h.counts.size == n_buckets          # no per-sample state
    assert h.count == 10000


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(2)
    a, b = rng.exponential(size=400), rng.exponential(size=700)
    ha, hb, hu = (StreamingHistogram() for _ in range(3))
    for v in a:
        ha.observe(v)
        hu.observe(v)
    for v in b:
        hb.observe(v)
        hu.observe(v)
    ha.merge(hb)
    assert ha.count == hu.count == 1100
    assert np.array_equal(ha.counts, hu.counts)
    assert ha.vmax == hu.vmax and ha.vmin == hu.vmin
    for q in (0.5, 0.99):
        assert ha.quantile(q) == hu.quantile(q)


def test_histogram_merge_rejects_incompatible_configs():
    with pytest.raises(AssertionError):
        StreamingHistogram(buckets_per_decade=32).merge(
            StreamingHistogram(buckets_per_decade=16))


def test_registry_snapshot_and_prometheus():
    r = MetricsRegistry()
    r.inc("requests.released", 3)
    r.gauge("queue_depth", 5.0)
    r.observe("ttft", 0.12)
    r.observe("ttft", 0.34)
    snap = r.snapshot()
    assert snap["schema"] == SCHEMA
    assert snap["counters"]["requests.released"] == 3
    assert snap["gauges"]["queue_depth"] == 5.0
    assert snap["histograms"]["ttft"]["count"] == 2
    text = r.prometheus_text()
    assert "tarragon_requests_released_total 3" in text
    assert "tarragon_queue_depth 5" in text
    assert 'tarragon_ttft_bucket{le="+Inf"} 2' in text
    assert "tarragon_ttft_count 2" in text


# --------------------------------------------------------------------------
# event bus: per-consumer cursors, nothing stolen
# --------------------------------------------------------------------------

def _ev(t, kind, worker="aw0"):
    return WorkerEvent(t, kind, worker)


def test_event_bus_multi_consumer_non_stealing():
    bus = EventBus()
    for i in range(3):
        bus.publish(_ev(float(i), "detected"))
    # two consumers each see the full stream
    assert len(bus.drain("a")) == 3
    assert len(bus.drain("b")) == 3
    assert len(bus.drain("a")) == 0            # cursor advanced, no repeat
    bus.publish(_ev(3.0, "provisioned"))
    assert [e.kind for e in bus.drain("a")] == ["provisioned"]
    assert [e.kind for e in bus.drain("b")] == ["provisioned"]
    # the underlying stream is still intact for late-joining consumers
    assert len(bus.events) == 4
    assert len(bus.drain("late")) == 4
    assert bus.cursor("a") == 4


def test_event_bus_cap_drops_newest_keeps_cursors_valid():
    """Past the cap the bus drops NEW events (counting them) rather than
    shifting old ones out — existing consumer cursors stay valid
    indices into an append-only stream."""
    bus = EventBus(max_events=4)
    for i in range(6):
        bus.publish(_ev(float(i), "k"))
    assert len(bus) == 4 and bus.dropped == 2
    assert [e.t for e in bus.drain("x")] == [0.0, 1.0, 2.0, 3.0]


def test_timeline_from_bus_is_a_second_consumer():
    bus = EventBus()
    bus.publish(WorkerEvent(0.5, "detected", "aw0", "heartbeat"))
    bus.publish(WorkerEvent(1.0, "provisioned", "aw2"))
    audit = bus.drain("audit")                 # first consumer
    lines = timeline_from_bus(bus)             # second, non-stealing
    assert len(audit) == 2
    assert lines == ["detected@0.50s aw0 (heartbeat)",
                     "provisioned@1.00s aw2"]
    assert timeline_from_bus(bus) == []        # own cursor advanced
    assert len(bus.events) == 2


# --------------------------------------------------------------------------
# stall attribution: clipped, prioritised, sums exactly
# --------------------------------------------------------------------------

def test_attribute_gap_sums_exactly_and_prioritises():
    comps = attribute_gap(0.0, 10.0, {
        "detection": [(-1.0, 3.0)],            # clipped to [0, 3]
        "queue_wait": [(2.0, 5.0)],            # [2,3] already claimed
        "prefill": [(4.5, 5.5)],               # [4.5,5] claimed by queue
    })
    assert comps["detection"] == pytest.approx(3.0)
    assert comps["queue_wait"] == pytest.approx(2.0)
    assert comps["prefill"] == pytest.approx(0.5)
    assert comps["execution"] == pytest.approx(4.5)
    assert sum(comps.values()) == pytest.approx(10.0, abs=1e-12)


def test_attribute_gap_empty_causes_is_all_execution():
    comps = attribute_gap(1.0, 2.5, {})
    assert comps["execution"] == pytest.approx(1.5)
    assert all(comps[c] == 0.0 for c in STALL_CAUSES)


# --------------------------------------------------------------------------
# the full scenario: AW failure + preemptions + queued cancel + prefix-warm
# chat turns, telemetry on vs off
# --------------------------------------------------------------------------

STEP = 0.02
PF_TOK = 0.002
_RUNS = {}


def _workload():
    slo = make_workload("mixed_slo", rate_rps=3.0, duration=2.0, seed=7,
                        max_new=40, interactive_deadline=0.3,
                        batch_wave=8, batch_every=3.0)
    chat = make_workload("multi_turn_chat", rate_rps=3.0, duration=2.0,
                         seed=11, chat_turns=2, chat_turn_gap=0.6,
                         chat_max_new=4)
    return sorted(slo + chat, key=lambda r: (r.arrival, r.request_id))


def record_restores(eng):
    """Keep, in ``eng.restored``, what ``CheckpointStore.restore_request``
    returned to each per-request restore (the calls made inside a
    ``restore.read`` span; the prefix plane's own restores are left
    out)."""
    eng.restored = []
    read = eng.store.restore_request

    def recording(rid):
        out = read(rid)
        tel = eng.telemetry
        if tel is not None and tel._open and \
                tel._open[-1].name == "restore.read":
            eng.restored.append((rid, out))
        return out
    eng.store.restore_request = recording


def scenario(telemetry: bool):
    """One serving run (cached per on/off) exercising every lifecycle
    path the plane traces: fresh admission, chunked prefill, preemption
    + requeue, AW failure + checkpoint restore, a prefix-warm chat turn,
    and a queued cancel."""
    if telemetry in _RUNS:
        return _RUNS[telemetry]
    cfg = reduced("mixtral_8x7b", cap_factor=4.0)
    ecfg = EngineConfig(max_batch=8, max_seq=96, num_aw=2, num_ew=2,
                        chunk_token_budget=16, prefix_cache_slots=4,
                        preempt=True, placement="session_affinity",
                        telemetry=telemetry, stall_threshold=0.1)
    eng = InferenceEngine(cfg, ecfg, jax.random.PRNGKey(1))
    record_restores(eng)
    orch = Orchestrator(eng, profile=TarragonProfile(detect=0.05,
                                                     detect_retries=2),
                        worker_init_time=0.5)
    # a request cancelled while still queued: no RequestState ever exists,
    # the root span must close through the drop path
    eng.gateway.enqueue("cx", np.arange(1, 9, dtype=np.int32), 4, now=0.0)
    assert eng.cancel_request("cx", now=0.0)
    m = run_serving(eng, _workload(), duration=60.0, orchestrator=orch,
                    failures=[FailurePlan(0.4, "aw", 0)],
                    step_time=STEP, prefill_token_time=PF_TOK)
    _RUNS[telemetry] = (eng, orch, m)
    return _RUNS[telemetry]


def test_scenario_covers_every_path():
    eng, orch, m = scenario(True)
    wl = _workload()
    assert len(m.finished) == len(wl)
    assert eng.gateway.stats.preemptions >= 1
    assert eng.gateway.stats.prefix_hits >= 1
    assert eng.store.stats.restores >= 1
    assert any(e.kind == "detected" for e in orch.events)


def test_telemetry_on_off_bit_identical():
    """The invariant the whole plane is built around: switching telemetry
    on cannot change a single token."""
    _, _, m_on = scenario(True)
    _, _, m_off = scenario(False)
    assert set(m_on.outputs) == set(m_off.outputs)
    for rid, toks in m_off.outputs.items():
        assert m_on.outputs[rid] == toks, rid
    assert m_on.finished == m_off.finished
    assert m_on.telemetry is not None and m_off.telemetry is None


def test_telemetry_mints_zero_new_jit_traces():
    eng_on, _, _ = scenario(True)
    eng_off, _, _ = scenario(False)

    def traces(eng):
        return eng._decode._cache_size() + eng.decode_plane.segment_traces()

    assert traces(eng_on) == traces(eng_off)
    # the host spans bracket the chunk calls, the checkpoint gathers and
    # the restores without tracing anything of their own
    assert {sp.name for sp in host_spans(eng_on.telemetry)} >= {
        "step", "chunk.device", "checkpoint.device", "recovery.restore"}
    for fn in ("_prefill_chunk", "_extract"):
        assert getattr(eng_on, fn)._cache_size() == \
            getattr(eng_off, fn)._cache_size(), fn
    assert eng_on.chunked._extract_range._cache_size() == \
        eng_off.chunked._extract_range._cache_size()
    # and the snapshot's own gauge agrees (sync() reads, never compiles)
    snap = eng_on.telemetry.snapshot()
    assert snap["gauges"]["jit.decode_traces"] == traces(eng_on)
    assert traces(eng_on) == eng_on._decode._cache_size() + \
        eng_on.decode_plane.segment_traces()


def test_every_request_closes_exactly_one_root_span():
    """Admitted, preempted, failed-over, prefix-warm, and queued-cancelled
    requests all close exactly one root span — none dangle, none double."""
    eng, _, m = scenario(True)
    tel = m.telemetry
    rids = {w.request_id for w in _workload()} | {"cx"}
    assert set(tel.closed_roots) == rids
    assert all(n == 1 for n in tel.closed_roots.values()), tel.closed_roots
    assert not tel._root                       # nothing left open
    assert not tel._phase
    snap = tel.snapshot()
    assert snap["spans"]["open_roots"] == 0
    assert snap["counters"]["requests.outcome.cancelled"] == 1
    assert snap["counters"]["requests.outcome.done"] == len(_workload())


def test_stall_components_sum_to_gap():
    _, _, m = scenario(True)
    rep = m.telemetry.stall_report()
    assert rep                                  # the failure forced stalls
    for s in rep:
        assert s["gap"] > m.telemetry.stall_threshold
        assert abs(sum(s["components"].values()) - s["gap"]) < 1e-9, s
        assert all(v >= -1e-12 for v in s["components"].values()), s
    causes = {c for s in rep
              for c, v in s["components"].items() if v > 1e-12}
    # the AW failure must be visible in the attribution: its victims'
    # stalls carry restore (failover requeue) time, and the preemption
    # plane's victims carry preemption time
    assert "restore" in causes, causes
    assert "preemption" in causes, causes
    assert "execution" in causes


def test_streamed_percentiles_match_exact_within_one_bucket():
    """The registry's O(1) histograms reproduce the exact per-token lists
    ServeMetrics keeps: identical counts, identical gap stream (p50 of
    TBT is exact), and every quantile within one log bucket of the order
    statistic."""
    _, _, m = scenario(True)
    tel = m.telemetry
    tbt_e, ttft_e = m.tbt_values(), m.ttft_values()
    h_tbt, h_ttft = tel.registry.hist("tbt"), tel.registry.hist("ttft")
    assert h_tbt.count == tbt_e.size           # same stream, same length
    assert h_ttft.count == ttft_e.size
    assert h_tbt.quantile(0.5) == pytest.approx(exact_rank(tbt_e, 0.5),
                                                rel=0.08)
    for h, vals in ((h_tbt, tbt_e), (h_ttft, ttft_e)):
        for q in (0.50, 0.95, 0.99):
            assert within_one_bucket(h, h.quantile(q),
                                     exact_rank(vals, q)), \
                (q, h.quantile(q), exact_rank(vals, q))
    # sums match too (histogram keeps a running total)
    assert h_tbt.total == pytest.approx(float(tbt_e.sum()), rel=1e-6)


def test_per_class_histograms_partition_the_stream():
    _, _, m = scenario(True)
    tel = m.telemetry
    classes = set(m.slo_class.values())
    assert {"interactive", "batch", "standard"} <= classes
    n_by_class = sum(tel.registry.hist(f"tbt.{c}").count for c in classes)
    assert n_by_class == tel.registry.hist("tbt").count
    for c in classes:
        assert tel.registry.hist(f"tbt.{c}").count == m.tbt_values(c).size


def test_snapshot_schema_and_mirrored_stats():
    eng, _, m = scenario(True)
    snap = m.telemetry.snapshot()
    assert snap["schema"] == SCHEMA
    for key in ("counters", "gauges", "histograms", "clock", "stalls",
                "spans"):
        assert key in snap, key
    gs = eng.gateway.stats
    assert snap["counters"]["gateway.preemptions"] == gs.preemptions
    assert snap["counters"]["gateway.prefix_hits"] == gs.prefix_hits
    assert snap["counters"]["events.preempted"] == gs.preemptions
    assert snap["gauges"]["gateway.queue_depth"] == 0
    assert snap["gauges"]["ew.live"] == len(eng.live_ews)
    # every admission (including the re-admissions of preempted and
    # failed-over requests) observed a queueing delay
    assert snap["histograms"]["queue_delay"]["count"] >= len(m.queue_delay)
    assert json.loads(json.dumps(snap)) == snap   # JSON-serialisable


def test_prometheus_export_shape():
    _, _, m = scenario(True)
    text = m.telemetry.prometheus_text()
    lines = text.splitlines()
    assert any(ln.startswith("tarragon_ttft_bucket{le=") for ln in lines)
    assert any('le="+Inf"' in ln for ln in lines)
    assert any(ln.startswith("tarragon_gateway_admitted_total ")
               for ln in lines)
    # cumulative bucket counts are monotone
    cum = [float(ln.rsplit(" ", 1)[1]) for ln in lines
           if ln.startswith("tarragon_tbt_bucket{")]
    assert cum == sorted(cum) and cum[-1] > 0


def test_chrome_trace_export(tmp_path):
    """Perfetto-loadable trace: process/thread metadata, complete spans
    with ts+dur on the virtual clock (µs), the failure's detection span
    on the workers track, and stall spans carrying their attribution."""
    eng, orch, m = scenario(True)
    path = tmp_path / "trace.json"
    trace = m.telemetry.export_chrome(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == trace
    evs = trace["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    det = [e for e in xs if e["name"].startswith("detect_aw")]
    assert len(det) == 1
    t_detect = next(e.t for e in orch.events if e.kind == "detected")
    assert det[0]["ts"] + det[0]["dur"] == pytest.approx(t_detect * 1e6)
    stall = [e for e in xs if e["name"].startswith("stall(")]
    assert stall
    assert any(e["args"].get("restore", 0) > 0 for e in stall)
    # every workload request has a root span event named after its rid
    names = {e["name"] for e in xs}
    assert {w.request_id for w in _workload()} <= names


def test_telemetry_off_engine_has_no_plane():
    eng, _, _ = scenario(False)
    assert eng.telemetry is None
    assert eng.gateway.telemetry is None
    # the bus still runs (it is the audit stream, not the telemetry plane)
    assert len(eng.bus.events) > 0


# --------------------------------------------------------------------------
# host spans and counters around the engine's own work
# --------------------------------------------------------------------------

def host_spans(tel, name=None):
    return [sp for sp in tel.tracer.spans if sp.cat == HOST and
            (name is None or sp.name == name)]


#: the span each host span is opened inside (None: a root)
PARENTS = {
    "step": {None},
    "step.hooks": {"step"}, "step.admit": {"step"}, "step.chunk": {"step"},
    "step.decode": {"step"}, "step.checkpoint": {"step"},
    "chunk.device": {"step.chunk"},
    "decode.device": {"step.decode"},
    "checkpoint.device": {"step.checkpoint"},
    "recovery.detect": {None},
    "recovery.ew_remap": {"recovery.detect"},
    "recovery.aw_requeue": {"recovery.detect"},
    # a restore runs where the request is admitted: in the detecting tick,
    # in a later step's admission pass, or when a provisioned AW drains
    # the queue
    "recovery.restore": {"recovery.aw_requeue", "step.admit", None},
    "restore.read": {"recovery.restore"},
    "restore.write": {"recovery.restore"},
}
_SEGMENTED = {}


def segmented_failover():
    """Decode segments of 4 steps, an EW then an AW lost mid-decode."""
    if not _SEGMENTED:
        cfg = reduced("mixtral_8x7b", cap_factor=4.0)
        ecfg = EngineConfig(max_batch=8, max_seq=96, num_aw=2, num_ew=2,
                            chunk_token_budget=16, decode_segment_len=4)
        eng = InferenceEngine(cfg, ecfg, jax.random.PRNGKey(2))
        record_restores(eng)
        orch = Orchestrator(eng, profile=TarragonProfile(
            detect=0.05, detect_retries=2), worker_init_time=0.5)
        wl = make_workload("random", rate_rps=8.0, duration=1.0, seed=5,
                           max_prompt=40, max_new=24)
        run_serving(eng, wl, duration=60.0, orchestrator=orch,
                    failures=[FailurePlan(0.3, "ew", 0),
                              FailurePlan(0.5, "aw", 0)],
                    step_time=STEP, prefill_token_time=PF_TOK)
        _SEGMENTED["run"] = eng
    return _SEGMENTED["run"]


def host_run(which: str):
    return scenario(True)[0] if which == "per_step" else \
        segmented_failover()


@pytest.mark.parametrize("which", ["per_step", "segmented"])
def test_host_spans_nest_under_their_parents(which):
    eng = host_run(which)
    spans = host_spans(eng.telemetry)
    by_id = {sp.sid: sp for sp in spans}
    seen = set()
    for sp in spans:
        parent = by_id.get(sp.parent)
        assert (parent.name if parent else None) in PARENTS[sp.name], \
            (sp.name, parent)
        assert sp.w0 <= sp.w1
        if parent is not None:
            assert parent.w0 <= sp.w0 and sp.w1 <= parent.w1, sp.name
        seen.add(sp.name)
    # the per-step scenario loses no EW
    assert set(PARENTS) - {"recovery.ew_remap"} <= seen
    assert which == "per_step" or "recovery.ew_remap" in seen
    # steps follow one another on the wall clock
    steps = [sp for sp in spans if sp.name == "step"]
    assert all(a.w1 <= b.w0 for a, b in zip(steps, steps[1:]))
    # the detect spans carry what failed
    assert {(sp.args["kind"], sp.args["worker"]) for sp in spans
            if sp.name == "recovery.detect"} >= {("aw", 0)}


@pytest.mark.parametrize("which", ["per_step", "segmented"])
def test_restore_spans_and_counters_match_the_store(which):
    """One recovery.restore per restored request, and its segments and
    bytes are what the store's restore_request handed back."""
    from repro.core.checkpoint import _seg_nbytes
    eng = host_run(which)
    tel = eng.telemetry
    spans = host_spans(tel, "recovery.restore")
    assert spans and len(spans) == len(eng.restored)
    want = [(rid, len(segs), sum(_seg_nbytes(x) for x in segs.values()))
            for rid, (_, _, segs) in eng.restored]
    assert [(sp.args["rid"], sp.args["segments"], sp.args["bytes"])
            for sp in spans] == want
    c = tel.registry.counters
    assert c["restore.segments"] == sum(n for _, n, _ in want) > 0
    assert c["restore.bytes"] == sum(b for _, _, b in want)
    # one compiled scatter call per block of rows
    k = eng.layout.block_tokens
    assert c["restore.cache_writes"] == sum(-(-n // k) for _, n, _ in want)
    # the store wrote what the engine captured
    assert c["checkpoint.bytes"] == eng.store.stats.bytes_written
    assert c["checkpoint.segments"] == eng.store.stats.updates


def decoding_engine(seg_len: int, checkpoint: bool):
    cfg = reduced("mixtral_8x7b", cap_factor=4.0)
    ecfg = EngineConfig(max_batch=4, max_seq=48, num_aw=2, num_ew=2,
                        decode_segment_len=seg_len, checkpoint=checkpoint)
    eng = InferenceEngine(cfg, ecfg, jax.random.PRNGKey(4))
    for i in range(3):
        eng.client.submit(RequestSpec(
            rid=f"r{i}", prompt=np.arange(1, 9, dtype=np.int32) + i,
            max_new=40))
    eng.step()            # admission and the whole-prompt prefill
    eng.step()
    assert len(eng.active_requests()) == 3
    return eng


@pytest.mark.parametrize("seg_len,checkpoint,drains", [
    # per-step: the dispatch-load counter, the token vector, the batched
    # checkpoint gather
    (1, True, 3),
    (1, False, 2),
    # a segment: the token ring (with its load counters), one multi-slot
    # checkpoint gather for the segment's KV
    (4, True, 2),
    (4, False, 1)])
def test_device_syncs_count_the_drains_of_a_step(seg_len, checkpoint,
                                                 drains):
    eng = decoding_engine(seg_len, checkpoint)
    assert eng.collect_load
    c = eng.telemetry.registry.counters
    for _ in range(3):
        before = c["step.device_syncs"]
        eng.step()
        assert c["step.device_syncs"] - before == drains
    # one checkpoint.device span per gather, inside its step.checkpoint
    names = [sp.name for sp in host_spans(eng.telemetry)]
    assert names.count("checkpoint.device") == \
        (names.count("step.checkpoint") if checkpoint else 0)


def test_chrome_trace_keeps_the_two_clocks_apart():
    eng, _, m = scenario(True)
    tr = m.telemetry.tracer
    virtual = tr.chrome_trace(clock_end=m.telemetry.now)
    wall = tr.chrome_trace(clock="wall")
    v_names = {e["name"] for e in virtual["traceEvents"] if e["ph"] == "X"}
    w_events = [e for e in wall["traceEvents"] if e["ph"] == "X"]
    w_names = {e["name"] for e in w_events}
    # host spans take no time on the caller's clock; spans recorded after
    # the fact (stall, detection window) have no wall stamps
    assert "step.decode" in w_names and "step.decode" not in v_names
    assert "detect_aw0" in v_names and "detect_aw0" not in w_names
    # wall microseconds are perf_counter's; every host event names its
    # parent
    host = [e for e in w_events if e["cat"] == HOST]
    assert host and all({"sid", "parent"} <= set(e["args"]) for e in host)
    lo = min(sp.w0 for sp in tr.spans if sp.w0 is not None)
    assert min(e["ts"] for e in w_events) == pytest.approx(lo * 1e6)
    with pytest.raises(ValueError):
        tr.chrome_trace(clock="both")


def test_live_planes_lists_the_planes_of_live_engines():
    eng = decoding_engine(1, True)
    plane = eng.telemetry
    assert any(p is plane for p in live_planes())
    del eng, plane
    gc.collect()
    n = len(live_planes())
    eng = decoding_engine(1, True)
    assert len(live_planes()) == n + 1
