"""Per-layer metrics, each read from one source of a trace run.

Each function takes the finished ``Run`` and returns a number, or None when
the run has nothing for it to read (the harness then leaves the metric out).
``bench/metrics/<name>.py`` binds a metric's name to one of these.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from harness import flops, trace
from harness.weights import dims


@dataclass
class Run:
    config: dict
    peaks: dict
    window_s: float
    served: object                       # harness.loop.Served
    queue_delay: Dict[str, float] = field(default_factory=dict)
    probes: dict = field(default_factory=dict)   # name -> loop.Probe
    trace: Optional[trace.Trace] = None
    traced: tuple = (float("-inf"), float("inf"))  # host bounds of trace


def _calls(run: Run, name: str, traced: bool = False):
    """A probe's calls; ``traced``: those inside the profiled span."""
    p = run.probes.get(name)
    calls = p.calls if p is not None else []
    if traced:
        lo, hi = run.traced
        calls = [c for c in calls if c[0] >= lo and c[1] <= hi]
    return calls


def _traced_seconds(run: Run) -> float:
    lo, hi = run.traced
    return hi - lo if hi > lo and hi != float("inf") else run.window_s


def queue_wait_p50_s(run: Run):
    """Gateway: median of admission minus due time (s)."""
    v = list(run.queue_delay.values())
    return float(np.percentile(v, 50)) if v else None


def _mean_ms(calls):
    return 1e3 * float(np.mean([t1 - t0 for t0, t1, _ in calls])) \
        if calls else None


def decode_step_ms(run: Run):
    """Engine step: mean host time of the jitted decode call to its end."""
    return _mean_ms(_calls(run, "decode"))


def prefill_chunk_ms(run: Run):
    """Engine step: mean host time of the jitted prefill-chunk call."""
    return _mean_ms(_calls(run, "chunk"))


def step_mfu(run: Run):
    """Model step: model FLOPs of the tokens processed in the traced span
    over that span at the chip's bf16 peak (%)."""
    c = run.config
    work = sum(flops.decode_step_flops(c, i["ctx"])
               for _, _, i in _calls(run, "decode", True))
    work += sum(flops.chunk_flops(c, i["rows"])
                for _, _, i in _calls(run, "chunk", True))
    if not work:
        return None
    return 100.0 * work / (_traced_seconds(run) *
                           run.peaks["bf16_flops_per_s"])


def _roofline(run: Run, kernel: str, least: float):
    if run.trace is None or not least:
        return None
    spent = trace.kernel_seconds(run.trace, kernel)
    return 100.0 * least / spent if spent > 0 else None


def moe_gemm_roofline(run: Run):
    """Kernels: least time of the expert FFN's routed work (weights of the
    experts reached, routed rows) over moe_gemm's device time (%)."""
    c, pk = run.config, run.peaks
    layers = dims(c)["L"]
    tokens = [len(i["ctx"]) for _, _, i in _calls(run, "decode", True)]
    tokens += [sum(n for _, n in i["rows"]) for _, _, i in
               _calls(run, "chunk", True)]
    least = sum(layers * flops.least_time(*flops.moe_gemm_cost(c, t), pk)
                for t in tokens if t)
    return _roofline(run, "moe_gemm", least)


def decode_attention_roofline(run: Run):
    """Kernels: least time of decode attention over the real context
    lengths over decode_attention_paged's device time (%)."""
    c, pk = run.config, run.peaks
    least = sum(dims(c)["L"] * flops.least_time(
        *flops.decode_attention_cost(c, i["ctx"]), pk)
        for _, _, i in _calls(run, "decode", True) if len(i["ctx"]))
    return _roofline(run, "decode_attention_paged", least)


def flash_attention_roofline(run: Run):
    """Kernels: least time of causal chunk attention over the real tokens
    over flash_attention's device time (%)."""
    c, pk = run.config, run.peaks
    least = sum(dims(c)["L"] * flops.least_time(
        *flops.flash_attention_cost(c, i["rows"]), pk)
        for _, _, i in _calls(run, "chunk", True) if i["rows"])
    return _roofline(run, "flash_attention", least)


def device_idle(run: Run):
    """Device: share of the traced window with no op running (%)."""
    if run.trace is None or not run.trace.devices or \
            run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace) /
                    run.trace.window_s)


def recovery_tick_ms(run: Run):
    """Recovery: mean host time of the orchestrator ticks that fired a
    detection (AW restore or EW remap)."""
    t = run.served.recovery_ticks
    return 1e3 * float(np.mean(t)) if t else None
