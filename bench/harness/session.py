"""One run of one cell: set-up, warm-up, the measured window, the reading of
the trace, the check of what was served, and the result line.

``run`` assumes the caller has checked the device (``bench/run.py``
refuses anything but a TPU); tests drive it on the CPU at small sizes.
"""
from __future__ import annotations

import gc
import json
import logging
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, loop, model, readers, spec, trace, traffic
from harness.weights import dims, make_weights

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a trace run profiles TRACE_SECONDS from TRACE_FROM of the way into the
# window: past the first arrivals, and in the failover cell across the
# second and third failures
TRACE_FROM, TRACE_SECONDS = 0.4, 15.0


class CompileCounter:
    """Programs compiled or loaded from the persistent cache in this
    process (JAX times both under one event), with the names of those made
    while ``names`` is on."""

    def __init__(self):
        self.count = 0
        self.names = []
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)
        self._handler = _Capture(self)
        logging.getLogger("jax").addHandler(self._handler)

    def _event(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.count += 1

    def watch(self, on: bool):
        self._on = on
        jax.config.update("jax_log_compiles", on)


class _Capture(logging.Handler):
    def __init__(self, counter):
        super().__init__(logging.WARNING)
        self.counter = counter

    def emit(self, record):
        if self.counter._on and "Compiling" in record.getMessage():
            self.counter.names.append(record.getMessage()[:160])


class DispatchCount:
    """Routed (token, expert) pairs the engine dispatched, summed from the
    per-slot load counter each jitted step returns; the pairs a step routes
    but drops for want of expert capacity are missing from it."""

    def __init__(self, eng):
        self.total = 0.0
        note = eng.note_dispatch_load

        def counted(load):
            self.total += float(np.asarray(load).sum())
            note(load)
        eng.note_dispatch_load = counted


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# warm-up: every program the window will use, and nothing else
# ---------------------------------------------------------------------------

def chunk_variants(eng):
    """(shape, capacity) of every prefill-chunk program the cell's budget
    can call: the chunk shapes of takes up to the budget, each at the
    capacity of every real-token count up to the budget."""
    ch = eng.chunked
    shapes = sorted({ch._shape_for(t) for t in range(1, ch.budget + 1)})
    caps = sorted({eng.prefill_capacity(r) for r in range(1, ch.budget + 1)})
    return [(s, c) for s in shapes for c in caps]


def warm_programs(eng):
    """Call each static variant of the engine's jitted entries once with
    every row idle (position -1), discarding the results."""
    b = eng.ecfg.max_batch
    rs = eng.route_state
    rs_pre = rs._replace(aw_health=jnp.ones_like(rs.aw_health))
    for shape, cap in chunk_variants(eng):
        jax.block_until_ready(eng._prefill_chunk(
            eng.params, jnp.zeros((b, shape), jnp.int32),
            jnp.full((b, shape), -1, jnp.int32), eng.cache, rs_pre,
            capacity=cap, with_load=eng.collect_load))
        jax.block_until_ready(eng.chunked._extract_range(
            eng.cache, 0, 0, count=shape))
    pos = jnp.full((b,), -1, jnp.int32)
    out = eng._decode(eng.params, jnp.zeros((b,), jnp.int32), pos, eng.cache,
                      rs, capacity=eng.decode_capacity,
                      with_load=eng.collect_load)
    jax.block_until_ready(eng.decode_plane.sample(out[0], pos))
    for n in range(1, b + 1):
        jax.block_until_ready(eng._extract(
            eng.cache, jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32)))


def warm_serving(eng, orch, cell: spec.Cell, vocab: int):
    """Serve a few requests through the window's own loop: admission,
    chunks, decode, checkpoints, release, page traffic; and where the mix
    loses workers, fail and heal one of each kind mid-decode."""
    e = cell.engine
    plen = min(e["chunk_token_budget"] + 3, e["max_seq"] // 2)
    rng = np.random.default_rng(0)
    reqs = [traffic.Request(f"warm{i}", 0.0,
                            rng.integers(0, vocab, plen, dtype=np.int32), 12)
            for i in range(e["max_batch"])]
    fails = [traffic.Failure(0.0, f["kind"], f["worker"])
             for f in cell.traffic.get("warmup_failures", [])]
    done = loop.drained(eng, orch)
    if fails:
        # fail once every request is decoding, so restores carry KV
        loop.serve(eng, orch, reqs, [], float("inf"),
                     stop=lambda s: len(s.stamps) == len(reqs) or done(s))
        reqs = []
    loop.serve(eng, orch, reqs, fails, float("inf"), stop=done)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def end_to_end(served: loop.Served, window: float) -> Dict[str, float]:
    """Every end-to-end number the harness can take from a window."""
    out = {}
    ttft = []
    for rid, due in served.due.items():
        st = served.stamps.get(rid)
        ttft.append((st[0] if st else window) - due)
    if ttft:
        out["ttft_p90_s"] = float(np.percentile(ttft, 90))
        out["ttft_p50_s"] = float(np.percentile(ttft, 50))
    gaps = np.concatenate([np.diff(st) for st in served.stamps.values()
                           if len(st) > 1] or [np.zeros(0)])
    if gaps.size:
        out["tbt_p99_ms"] = float(np.percentile(gaps, 99)) * 1e3
    emitted = sum(len(st) for st in served.stamps.values())
    out["tokens_per_s"] = (served.prefill_tokens + emitted) / window
    stalls = []
    at = [t for _, t in served.injected] + [window]
    for (f, at_t), until in zip(served.injected, at[1:]):
        stall = failure_stall(served, at_t, until)
        log(f"[failure] {f.kind}{f.worker} at {at_t:.3f} s: longest wait "
            f"{stall}")
        if stall is not None:
            stalls.append(stall)
    if stalls:
        out["failure_stall_s"] = float(np.mean(stalls))
    return out


def failure_stall(served: loop.Served, t: float, until: float):
    """Longest gap between consecutive tokens, among requests unfinished at
    the failure at ``t`` that had a token before it, over the gaps that end
    after ``t`` and by ``until`` (the next failure or the window's end); a
    request with no token by ``until`` counts the wait to it. The failed
    worker keeps serving until the orchestrator detects the failure, so the
    stall is the longest gap after the failure, not the first one."""
    worst = None
    for rid, st in served.stamps.items():
        if not st or st[0] > t or served.finished.get(rid, until + 1) <= t:
            continue
        last = max(x for x in st if x <= t)
        ends = [x for x in st if t < x <= until]
        prev = [last] + ends
        waits = [b - a for a, b in zip(prev, ends)]
        if served.finished.get(rid, until + 1) > until:
            waits.append(until - prev[-1])
        wait = max(waits)
        worst = wait if worst is None else max(worst, wait)
    return worst


def setup(cell: spec.Cell, seed: int, t_process: float, counter):
    """The served stack with the seed's weights, every program warm."""
    c = cell.config
    eng, orch, w = model.build(c, cell.config_name, seed,
                               lambda: make_weights(c, seed))
    log(f"[setup] weights and engine: {time.monotonic() - t_process:.3f} s "
        f"since process start, {counter.count} programs")
    warm_programs(eng)
    warm_serving(eng, orch, cell, dims(c)["V"])
    eng.gateway.stats.queue_delay.clear()
    return eng, orch, w


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        t_process: float, device: dict, peaks: dict,
        fault: Optional[Callable] = None, control: bool = False,
        on_check: Optional[Callable] = None) -> dict:
    """One run; returns the result line's object. ``fault(eng)`` breaks the
    engine under the window, ``control`` also reads the float8 control's
    gap over the same sample, and ``on_check(w, c, served, rids)`` is
    handed the sample once it is checked; all three are for
    ``bench/readings.py`` and the tests, never for the benchmark's own
    runs."""
    counter = CompileCounter()
    c = cell.config
    d = dims(c)
    eng, orch, w = setup(cell, seed, t_process, counter)
    reqs = traffic.generate(cell.traffic, seed, seconds, d["V"])
    fails = traffic.failures(cell.traffic, seconds)
    if fault is not None:
        fault(eng)
    probes = loop.install_probes(eng) if traced else {}
    dispatched = DispatchCount(eng)
    span = None
    if traced:
        span = loop.TraceSpan(tempfile.mkdtemp(prefix="bench-trace-"),
                                TRACE_FROM * seconds,
                                min(TRACE_SECONDS, 0.6 * seconds))
    n_setup = counter.count
    counter.watch(True)
    t_open = time.monotonic()
    served = loop.serve(eng, orch, reqs, fails, seconds, traced=span)
    counter.watch(False)
    setup_s = t_open - t_process
    in_window = counter.count - n_setup
    log(f"[setup] setup_s {setup_s:.3f}: {n_setup} programs compiled or "
        f"loaded before the window")
    log(f"[window] {in_window} programs compiled or loaded in the window "
        f"(expected 0)" + "".join(f"\n  {n}" for n in counter.names[:10]))
    window = served.end
    stats = jax.devices()[0].memory_stats() or {}
    dev = dict(device, memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                       0)))
    queue_delay = {r: v for r, v in eng.gateway.stats.queue_delay.items()
                   if r in served.due}
    attempted = len(served.due)
    n_tokens = sum(len(v) for v in served.stamps.values())
    log(f"[window] {window:.3f} s: {attempted} requests due, "
        f"{len(served.finished)} finished, {n_tokens} tokens, "
        f"{served.prefill_tokens} prompt tokens prefilled, generator late "
        f"by at most {served.late_s:.4f} s, failures "
        f"{[(f.kind, f.worker, round(t, 3)) for f, t in served.injected]}")

    result_metrics = {}
    breakdown = None
    if traced:
        t_tr = time.monotonic()
        tr = trace.load(trace.xplane_path(span.log_dir))
        log(f"[trace] {span.t1 - span.t0:.3f} s from {span.started:.3f} s "
            f"into the window (asked: {span.seconds:.1f} s from "
            f"{span.start:.1f} s), read in {time.monotonic() - t_tr:.1f} s")
        shutil.rmtree(span.log_dir, ignore_errors=True)
        lost = trace.lost_device_ops(tr)
        if lost is not None:
            raise RuntimeError(
                f"the device trace ends {tr.t1 - lost:.3f} s before the "
                "traced span does, with engine calls after it: the "
                "profiler's device buffer overflowed, and no device metric "
                "of this run would be sound")
        rd = readers.Run(c, peaks, window, served, queue_delay, probes, tr,
                         (span.t0, span.t1))
        for m in cell.per_layer:
            v = spec.metric_reader(m.name)(rd)
            if v is not None:
                result_metrics[m.name] = {"value": v, "unit": m.unit}
        busy = trace.busy_seconds(tr)
        dev.update(busy_s=busy, window_s=tr.window_s)
        log(f"[trace] busy {busy:.4f} of {tr.window_s:.4f} s; kernel s: " +
            ", ".join(f"{k} {trace.kernel_seconds(tr, k):.4f}"
                      for k in trace.KERNELS))
        for name, secs in trace.top_ops(tr, 25):
            log(f"[trace] op {secs:.5f} s {name}")
        breakdown = {"device_ops": trace.top_ops(tr),
                     "idle_gaps": trace.idle_gaps(tr)}
    else:
        e2e = end_to_end(served, window)
        e2e["setup_s"] = setup_s
        log(f"[e2e] {json.dumps(e2e)}")
        for m in cell.end_to_end:
            if m.name in e2e:
                result_metrics[m.name] = {"value": e2e[m.name],
                                          "unit": m.unit}

    # the program's state goes before the reference runs on the chip
    del eng, orch, probes
    gc.collect()
    struck = set().union(*served.struck.values()) if served.struck else ()
    rids = check.sample(served, seed, struck)
    t_ck = time.monotonic()
    got = check.compare(w, c, served, rids)
    limit = c["check"]["clipped_mean_gap"]
    log(f"[check] {len(rids)} requests, {got['tokens']} served tokens "
        f"against the float32 reference in {time.monotonic() - t_ck:.1f} s;"
        f" widest gap {got['max_gap']}, mean gap {got['mean_gap']}, "
        f"mismatch share {got['mismatch_share']} (not compared)")
    # every token of the window routed to top-k experts in every layer
    routed = d["K"] * d["L"] * (served.prefill_tokens + n_tokens)
    dropped = int(round(routed - dispatched.total))
    correct = got["tokens"] > 0 and got["clipped_mean_gap"] <= limit and \
        dropped == 0
    compared = {"clipped_mean_gap": {"value": got["clipped_mean_gap"],
                                     "limit": limit},
                "dropped_expert_routes": {"value": dropped, "limit": 0}}
    if control:
        ctl = check.compare(w, c, served, rids, control=True)
        compared["control_clipped_mean_gap"] = {
            "value": ctl["clipped_mean_gap"], "limit": limit}
        log(f"[control] {json.dumps({'program': got, 'control': ctl})}")
    if on_check is not None:
        on_check(w, c, served, rids)
    for k, v in compared.items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": result_metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out

