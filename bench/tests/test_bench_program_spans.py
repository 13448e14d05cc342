"""Per-layer metrics read from the program's own host spans: a small
traced run of each cell reads its own two metrics as finite numbers and
leaves the other cell's out; a span outside the traced bounds is not
read; a program without the spans gives nothing to read."""
import copy
import math
import types

import pytest
from bench_tiny import paced, run_small, small_cell

from harness import program_spans, readers
from harness.loop import Served
from repro.serving import telemetry

OWN = {"mixtral-8x7b-2l.chat": ("step_host_ms.chat", "checkpoint_ms.chat"),
       "mixtral-8x7b-2l.failover": ("restore_request_ms.failover",
                                    "restore_mb_per_s.failover")}


@pytest.fixture(scope="module", params=sorted(OWN))
def traced(request):
    cell = copy.deepcopy(small_cell(request.param))
    cell.config["torch_dtype"] = "float32"
    fault = None
    if cell.traffic["failures"]:
        # answers long enough that both AWs hold decoding requests when
        # the last failure strikes, inside the traced part of the window
        cell.traffic["output_tokens"] = {"median": 40, "sigma": 0.2,
                                         "min": 30, "max": 60}
        fault = paced
    return request.param, run_small(cell, 6, seconds=3.0, traced=True,
                                    fault=fault)


def test_each_cell_reads_its_own_span_metrics(traced):
    name, out = traced
    assert out["correct"] is True
    for m in OWN[name]:
        v = out["metrics"][m]["value"]
        assert math.isfinite(v) and v > 0, (m, v)
    others = {m for cell, ms in OWN.items() if cell != name for m in ms}
    assert not others & set(out["metrics"])


T = 1e9          # far from any perf_counter reading of this process
R = 1e-4         # stamps near 1e9 s keep some 0.1 us of precision


def synthetic_plane():
    """A plane holding hand-stamped host spans: a step inside the bounds
    [T, T + 10] with a device call and a checkpoint, a restore inside and
    one outside, a step outside."""
    eng = types.SimpleNamespace(ecfg=types.SimpleNamespace())
    plane = telemetry.TelemetryPlane(eng)
    tr = plane.tracer

    def add(name, w0, w1, parent=None, **args):
        sp = tr.new("engine", name, 0.0, telemetry.HOST, args,
                    parent.sid if parent is not None else -1)
        sp.w0, sp.w1, sp.t1 = T + w0, T + w1, 0.0
        return tr.add(sp)

    step = add("step", 1.0, 1.010)
    dec = add("step.decode", 1.001, 1.007, step)
    add("decode.device", 1.002, 1.006, dec)
    add("step.checkpoint", 1.007, 1.009, step)
    add("step", 11.0, 11.5)
    add("recovery.restore", 2.0, 2.5, bytes=2_000_000)
    add("recovery.restore", 12.0, 13.0, bytes=5)
    return plane


def run_in(bounds):
    return readers.Run({}, {}, 10.0, Served(), traced=bounds)


def test_spans_outside_the_traced_bounds_are_left_out():
    plane = synthetic_plane()   # noqa: F841  (alive while read)
    run = run_in((T, T + 10.0))
    assert program_spans.step_host_ms(run) == pytest.approx(6.0, rel=R)
    assert program_spans.checkpoint_ms(run) == pytest.approx(2.0, rel=R)
    assert program_spans.restore_request_ms(run) == pytest.approx(500.0, rel=R)
    assert program_spans.restore_mb_per_s(run) == pytest.approx(4.0, rel=R)
    empty = run_in((T + 20.0, T + 30.0))
    for read in (program_spans.step_host_ms, program_spans.checkpoint_ms,
                 program_spans.restore_request_ms,
                 program_spans.restore_mb_per_s):
        assert read(empty) is None


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    plane = synthetic_plane()   # noqa: F841
    monkeypatch.delattr(telemetry, "live_planes")
    run = run_in((T, T + 10.0))
    assert program_spans.step_host_ms(run) is None
    assert program_spans.restore_mb_per_s(run) is None
