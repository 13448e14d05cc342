"""From a profiler trace to numbers: device busy time, kernel time, idle gaps.

A trace run wraps the window in ``jax.profiler.start_trace`` and a host span
``harness.window``. ``load`` reads the ``.xplane.pb`` the profiler wrote into
plain ``Event`` lists on one clock (seconds); everything else here works on
those lists, so it can be checked on synthetic events without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# device op names of the served Pallas kernels
KERNELS = {
    "moe_gemm": re.compile(r"moe_gemm|moe_ffn"),
    "decode_attention_paged": re.compile(r"decode_attention_paged"),
    "flash_attention": re.compile(r"flash_attention|flash_attn"),
}
WINDOW_SPAN = "harness.window"


@dataclass
class Event:
    name: str
    start: float    # seconds on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)   # host spans
    t0: float = 0.0
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


_OP = re.compile(r"%?([\w.\-]+)")
# ops that contain other ops of the same line: counted by their insides
CONTAINERS = re.compile(r"^(while|conditional|call)([.:]|$)")


def op_name(text: str) -> str:
    """``%moe_gemm.11 = f32[...] custom-call(...)`` -> ``moe_gemm.11``: a
    TPU trace names each op by its HLO instruction text."""
    m = _OP.match(text)
    return m.group(1) if m else text[:80]


def xplane_path(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str, span_prefixes=("harness.", "jit.")) -> Trace:
    """Device ops of every TPU plane ("XLA Ops" lines) and the host spans
    whose names start with one of ``span_prefixes``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append(Event(op_name(ev.name), s,
                                     s + ev.duration_ns * 1e-9))
            tr.devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefixes):
                        s = ev.start_ns * 1e-9
                        tr.spans.append(Event(ev.name, s,
                                              s + ev.duration_ns * 1e-9))
    win = [e for e in tr.spans if e.name == WINDOW_SPAN]
    if win:
        tr.t0, tr.t1 = win[0].start, win[0].end
    return tr


def clip(events: List[Event], t0: float, t1: float) -> List[Event]:
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by any event."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    if not tr.devices:
        return 0.0
    per = [sum(b - a for a, b in union(clip(ops, tr.t0, tr.t1)))
           for ops in tr.devices.values()]
    return sum(per) / len(per)


def kernel_seconds(tr: Trace, kernel: str) -> float:
    """Device seconds of one served kernel's ops in the window, summed over
    devices and averaged per device."""
    pat = KERNELS[kernel]
    if not tr.devices:
        return 0.0
    per = [sum(e.dur for e in clip(ops, tr.t0, tr.t1)
               if pat.search(e.name))
           for ops in tr.devices.values()]
    return sum(per) / len(per)


def lost_device_ops(tr: Trace, slack: float = 0.005) -> Optional[float]:
    """Where the device trace stops short of the host's: the start (on the
    trace's clock) of the first host ``jit.`` span in the window -- a call
    timed to the end of its device work -- that begins after the last op a
    device recorded; None where no such span exists. The profiler keeps a
    bounded buffer of device events and drops what overflows it without a
    word, which would read as idle time. A trace with no device plane (the
    CPU) has nothing to cut short."""
    calls = [s for s in tr.spans if s.name.startswith("jit.")
             and tr.t0 <= s.start < tr.t1]
    if not calls or not tr.devices:
        return None
    last = min(max((e.end for e in ops), default=tr.t0)
               for ops in tr.devices.values())
    after = [s.start for s in calls if s.start > last + slack]
    return min(after) if after else None


def _base(name: str) -> str:
    return re.sub(r"[.:]\d+$", "", name)


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """The device ops that took most time (names without instance
    suffixes, loops left out for their insides), seconds per device."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in tr.devices.values():
        for e in clip(ops, tr.t0, tr.t1):
            if not CONTAINERS.match(e.name):
                tot[_base(e.name)] += e.dur / len(tr.devices)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _open_span(spans: List[Event], t: float) -> Optional[Event]:
    inside = [s for s in spans if s.start <= t < s.end
              and s.name != WINDOW_SPAN]
    return min(inside, key=lambda s: s.dur) if inside else None


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """The longest stretches with no op on the first device, each named by
    the innermost host span open in its middle."""
    if not tr.devices:
        return []
    ops = next(iter(tr.devices.values()))
    busy = union(clip(ops, tr.t0, tr.t1))
    edges = [tr.t0] + [x for ab in busy for x in ab] + [tr.t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        sp = _open_span(tr.spans, (a + b) / 2)
        out.append([sp.name if sp else "no harness span", b - a])
    return out
