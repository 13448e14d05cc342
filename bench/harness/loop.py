"""The serving loop on the wall clock, open loop.

A wall-clock copy of ``repro.serving.scheduler.run_serving``'s loop: each
pass injects the failures that are due, advances the orchestrator, hands
every request that is due to the gateway stamped with its due time, and
runs one engine step. ``now`` is ``time.monotonic()`` since the window
opened; each token is stamped when the step that produced it returns, so
latency counts from the request's due time, whatever the loop was doing.

The loop's phases are host spans (``jax.profiler.TraceAnnotation``) named
``harness.<phase>``, so a profiler trace can tell what the host did in each
device idle gap. Trace runs profile part of the window (``TraceSpan``) and
wrap the engine's jitted entries (``Probe``) to time each call to its end
and keep what it was given.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List

import jax
import numpy as np

from harness.trace import WINDOW_SPAN
from harness.traffic import Failure, Request


class Probe:
    """One jitted engine entry, timed to ``block_until_ready`` inside a
    host span; ``note(args, kwargs)`` keeps what each call was given."""

    def __init__(self, name: str, fn, note: Callable):
        self.name, self.fn, self.note = name, fn, note
        self.calls: List[tuple] = []     # (t0, t1, note) on the host clock

    def __call__(self, *args, **kw):
        info = self.note(args, kw)
        with jax.profiler.TraceAnnotation(self.name):
            t0 = time.perf_counter()
            out = jax.block_until_ready(self.fn(*args, **kw))
            t1 = time.perf_counter()
        self.calls.append((t0, t1, info))
        return out


def _decode_note(args, kw):
    """Context length of each decoding row (pos + 1; pos -1 = idle row)."""
    pos = np.asarray(args[2])
    return {"ctx": (pos[pos >= 0] + 1).astype(np.int64)}


def _chunk_note(args, kw):
    """Per row in the chunk call: its first position and real tokens."""
    pos = np.asarray(args[2])
    rows = []
    for row in pos:
        real = row[row >= 0]
        if real.size:
            rows.append((int(real[0]), int(real.size)))
    return {"rows": rows, "shape": int(pos.shape[1]),
            "capacity": kw.get("capacity")}


def install_probes(eng) -> Dict[str, Probe]:
    """Wrap the engine's decode and prefill-chunk entries (trace runs)."""
    probes = {"decode": Probe("jit.decode", eng._decode, _decode_note)}
    eng._decode = probes["decode"]
    if eng.chunked is not None:
        probes["chunk"] = Probe("jit.prefill_chunk", eng._prefill_chunk,
                                _chunk_note)
        eng._prefill_chunk = probes["chunk"]
    return probes


@dataclass
class Served:
    """What one window served, on its own clock (seconds since it opened)."""
    due: Dict[str, float] = field(default_factory=dict)
    prompt_len: Dict[str, int] = field(default_factory=dict)
    stamps: Dict[str, List[float]] = field(
        default_factory=lambda: defaultdict(list))
    outputs: Dict[str, List[int]] = field(default_factory=dict)  # finished
    partial: Dict[str, List[int]] = field(default_factory=dict)  # in flight
    prompts: Dict[str, np.ndarray] = field(default_factory=dict)
    finished: Dict[str, float] = field(default_factory=dict)
    injected: List[tuple] = field(default_factory=list)  # (Failure, t)
    recovery_ticks: List[float] = field(default_factory=list)  # seconds
    struck: Dict[float, set] = field(default_factory=dict)  # at -> rids
    prefill_tokens: int = 0
    end: float = 0.0                 # when the last step returned
    late_s: float = 0.0              # worst lag of an enqueue behind due

    def tokens(self) -> Dict[str, List[int]]:
        """Every request's served tokens: finished, and in flight at the
        end of the window."""
        return {**self.partial, **self.outputs}


class TraceSpan:
    """Profile ``seconds`` of the window from ``start`` seconds in: the
    device keeps a bounded trace buffer, and a whole window of this
    serving loop overflows it, so a part of the window is traced. The span
    opens and closes between passes of the loop, so it runs from the first
    pass at or after ``start`` (``started``) to the first pass at or after
    its end: a pass that a restore holds up lengthens it. Every reading
    takes its actual bounds, ``t0`` and ``t1``."""

    def __init__(self, log_dir: str, start: float, seconds: float):
        self.log_dir, self.start, self.seconds = log_dir, start, seconds
        self.t0 = self.t1 = None         # host perf_counter bounds
        self.started = None              # seconds into the window
        self._span = None

    def tick(self, now: float):
        if self.t0 is None and now >= self.start:
            jax.profiler.start_trace(self.log_dir)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self.t0 = time.perf_counter()
            self.started = now
        elif self._span is not None and now >= self.started + self.seconds:
            self.close()

    def close(self):
        if self._span is not None:
            self.t1 = time.perf_counter()
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def serve(eng, orch, reqs: List[Request], fails: List[Failure],
          seconds: float, *, stop: Callable = None, traced: TraceSpan = None,
          clock=time.monotonic) -> Served:
    """Drive ``reqs`` (in due order) and ``fails`` through the engine for
    ``seconds`` of wall clock, or (warm-up) until ``stop(served)`` holds
    once every request is enqueued and every failure injected; profile the
    part of the window ``traced`` names."""
    gw = eng.gateway
    span = jax.profiler.TraceAnnotation
    s = Served()
    fails = sorted(fails, key=lambda f: f.at)
    qi = fi = 0
    pf0 = eng.prefill_tokens_done()
    t0 = clock()
    now = 0.0
    while True:
        now = clock() - t0
        if now >= seconds or (stop is not None and qi == len(reqs) and
                              fi == len(fails) and stop(s)):
            break
        if traced is not None:
            traced.tick(now)
        while fi < len(fails) and fails[fi].at <= now:
            f = resolve(eng, fails[fi])
            # the requests this failure strikes: an AW's own requests are
            # restored elsewhere; every live request decodes through
            # shadow experts while an EW is down
            s.struck[f.at] = {r.rid for r in eng.requests.values()
                              if not r.done and
                              (f.kind == "ew" or r.aw == f.worker)}
            orch.inject_failure(f.kind, f.worker, now)
            s.injected.append((f, now))
            fi += 1
        with span("harness.orchestrator_tick"):
            ts = time.perf_counter()
            fired = orch.tick(now)
            te = time.perf_counter()
        if any(ev.kind == "detected" for ev in fired):
            s.recovery_ticks.append(te - ts)
        with span("harness.enqueue"):
            while qi < len(reqs) and reqs[qi].due <= now:
                r = reqs[qi]
                gw.enqueue(r.rid, r.prompt, r.max_new, now=r.due)
                s.due[r.rid] = r.due
                s.prompt_len[r.rid] = len(r.prompt)
                s.prompts[r.rid] = r.prompt
                s.late_s = max(s.late_s, now - r.due)
                qi += 1
        with span("harness.engine_step"):
            out = eng.step(now)
        t_ret = clock() - t0
        for rid, toks in out.items():
            s.stamps[rid].extend([t_ret] * len(toks))
        with span("harness.release"):
            for r in list(eng.requests.values()):
                if r.done:
                    s.outputs[r.rid] = list(r.tokens)
                    s.finished[r.rid] = t_ret
                    eng.release_request(r.rid)
        s.end = t_ret
        if not out and _idle(eng, orch):
            nxt = [seconds, now + 0.05]
            if qi < len(reqs):
                nxt.append(reqs[qi].due)
            if fi < len(fails):
                nxt.append(fails[fi].at)
            with span("harness.wait_for_arrival"):
                time.sleep(max(0.0, min(nxt) - (clock() - t0)))
    if traced is not None:
        traced.close()
    s.end = max(s.end, now)
    # a request that fell due while the loop was held up (a restore blocks
    # it for seconds) and never reached the gateway is still attempted:
    # it counts with no token
    while qi < len(reqs) and reqs[qi].due < s.end:
        s.due[reqs[qi].rid] = reqs[qi].due
        qi += 1
    s.prefill_tokens = eng.prefill_tokens_done() - pf0
    s.partial = {r.rid: list(r.tokens) for r in eng.requests.values()
                 if r.tokens and r.rid in s.due}
    return s


def protected_ew(eng):
    """The EW whose every expert has a live replica on another EW in the
    installed placement plan: the one failure the shadows cover now."""
    plan = eng.placement_mgr.plan
    alive = eng.live_ews
    for m in sorted(alive):
        mine = [e for e in range(plan.num_experts)
                if plan.slot_owner[plan.primary[e]] == m]
        if mine and all(any(plan.slot_expert[j] == e and
                            plan.slot_owner[j] not in (m, -1) and
                            plan.slot_owner[j] in alive
                            for j in range(plan.num_slots)) for e in mine):
            return m
    return None


def resolve(eng, f: Failure) -> Failure:
    """A failure whose worker is "protected" strikes the EW the shadow
    experts cover at that moment."""
    if f.worker != "protected":
        return f
    m = protected_ew(eng)
    if m is None:
        raise RuntimeError("no EW is covered by shadow experts")
    return Failure(f.at, f.kind, m)


def drained(eng, orch) -> Callable:
    """A ``stop`` for ``serve``: no request left anywhere, no recovery
    outstanding."""
    return lambda s: _idle(eng, orch) and not eng.requests


def _idle(eng, orch) -> bool:
    return not eng.active_requests() and not eng.prefilling_requests() \
        and eng.gateway.depth() == 0 and orch.outstanding == 0
