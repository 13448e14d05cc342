"""Per-layer metrics read from the program's own host spans.

The engine's telemetry plane (``repro.serving.telemetry``) records a host
span around each engine step and its phases, the KV checkpoint capture,
each failure it handles and each per-request restore. Every span carries
wall stamps ``w0``/``w1`` on ``time.perf_counter``, the clock of the
harness's ``Probe`` calls and ``TraceSpan`` bounds, and the id of its
parent span. A reader takes the planes alive in the process
(``live_planes()``) and keeps the spans whose stamps fall inside the
traced bounds, as the probe readers keep their calls.

A program without those spans (no ``live_planes``, spans without wall
stamps, or the plane switched off) gives every reader nothing to read:
each returns None.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

DEVICE_SUFFIX = ".device"   # a span around device-bound work and its drain


def traced_spans(run) -> List[Dict[int, object]]:
    """Per live plane, its host spans inside ``run.traced`` by id."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return []
    live = getattr(telemetry, "live_planes", None)
    if live is None:
        return []
    lo, hi = run.traced
    out = []
    for plane in live():
        spans = {}
        for sp in plane.tracer.spans:
            w0, w1 = getattr(sp, "w0", None), getattr(sp, "w1", None)
            if sp.cat == "host" and w0 is not None and w1 is not None and \
                    lo <= w0 and w1 <= hi:
                spans[sp.sid] = sp
        if spans:
            out.append(spans)
    return out


def named(run, name: str) -> list:
    return [sp for spans in traced_spans(run) for sp in spans.values()
            if sp.name == name]


def _mean_ms(spans):
    return 1e3 * float(np.mean([sp.wall for sp in spans])) if spans else None


def step_host_ms(run):
    """Engine step: mean over the traced ``step`` spans of the step's wall
    time less its ``*.device`` descendants (the device-bound calls and
    their drains): the host time of the step (ms)."""
    host = []
    for spans in traced_spans(run):
        device: Dict[int, float] = {}
        for sp in spans.values():
            if not sp.name.endswith(DEVICE_SUFFIX):
                continue
            up = spans.get(sp.parent)
            while up is not None and up.name != "step":
                if up.name.endswith(DEVICE_SUFFIX):
                    break            # counted with the outer device span
                up = spans.get(up.parent)
            if up is not None and up.name == "step":
                device[up.sid] = device.get(up.sid, 0.0) + sp.wall
        host += [sp.wall - device.get(sp.sid, 0.0) for sp in spans.values()
                 if sp.name == "step"]
    return 1e3 * float(np.mean(host)) if host else None


def checkpoint_ms(run):
    """Recovery, checkpoint capture: mean wall time of the traced
    ``step.checkpoint`` spans, one per decode step (ms)."""
    return _mean_ms(named(run, "step.checkpoint"))


def restore_request_ms(run):
    """Recovery: mean wall time of the traced ``recovery.restore`` spans,
    one per restored request (ms)."""
    return _mean_ms(named(run, "recovery.restore"))


def restore_mb_per_s(run):
    """Recovery: bytes the traced ``recovery.restore`` spans restored over
    their summed wall time (MB/s, 1 MB = 1e6 bytes)."""
    spans = named(run, "recovery.restore")
    secs = sum(sp.wall for sp in spans)
    if not spans or secs <= 0:
        return None
    return sum(sp.args.get("bytes", 0) for sp in spans) / secs / 1e6
