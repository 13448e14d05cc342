"""The reduction from a profiler trace to device busy time, kernel time,
roofline share and the breakdown, on synthetic events and on a trace the
CPU profiler writes."""
import os
import tempfile

import jax
import jax.numpy as jnp
import pytest
from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)

from harness import flops, readers, trace
from harness.loop import Served
from harness.trace import Event, Trace

DEV = "/device:TPU:0"


def synthetic() -> Trace:
    ops = [Event("moe_gemm.1", 1.0, 3.0),
           Event("fusion.2", 2.5, 4.0),
           Event("decode_attention_paged.3", 6.0, 7.0),
           Event("fusion.4", 11.0, 12.0),     # after the window
           Event("while.5", 0.5, 4.0)]         # holds the others
    spans = [Event("harness.window", 0.0, 10.0),
             Event("harness.engine_step", 3.5, 6.5),
             Event("harness.wait_for_arrival", 7.0, 10.0)]
    return Trace({DEV: ops}, spans, 0.0, 10.0)


def test_busy_is_the_union_inside_the_window():
    assert trace.union(synthetic().devices[DEV][:2]) == [(1.0, 4.0)]
    assert trace.busy_seconds(synthetic()) == pytest.approx(4.5)


def test_op_names_come_from_the_hlo_text():
    assert trace.op_name("%moe_gemm.11 = f32[16,128,4096]{2,1,0} "
                         "custom-call(s32[16]{0} %b)") == "moe_gemm.11"
    assert trace.op_name("%dynamic-slice_bitcast_fusion.8 = bf16[8]") == \
        "dynamic-slice_bitcast_fusion.8"


def test_kernel_seconds_and_top_ops():
    tr = synthetic()
    assert trace.kernel_seconds(tr, "moe_gemm") == pytest.approx(2.0)
    assert trace.kernel_seconds(tr, "flash_attention") == 0.0
    assert trace.top_ops(tr) == [["moe_gemm", 2.0], ["fusion", 1.5],
                                 ["decode_attention_paged", 1.0]]


def test_idle_gaps_name_the_open_span():
    assert trace.idle_gaps(synthetic()) == [
        ["harness.wait_for_arrival", pytest.approx(3.0)],
        ["harness.engine_step", pytest.approx(2.0)],
        ["no harness span", pytest.approx(0.5)]]


def run_of(tr, probes, config):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return readers.Run(config, peaks, 10.0, Served(), {}, probes, tr)


class FakeProbe:
    def __init__(self, calls):
        self.calls = calls


def test_readers_on_a_synthetic_run():
    from test_bench_flops import MIXTRAL
    tr = synthetic()
    decode = FakeProbe([(0.0, 0.1, {"ctx": [100, 50]})])
    run = run_of(tr, {"decode": decode}, MIXTRAL)
    assert readers.device_idle(run) == pytest.approx(55.0)
    least = 2 * flops.least_time(*flops.moe_gemm_cost(MIXTRAL, 2),
                                 run.peaks)
    assert readers.moe_gemm_roofline(run) == pytest.approx(100 * least / 2.0)
    least = 2 * flops.least_time(
        *flops.decode_attention_cost(MIXTRAL, [100, 50]), run.peaks)
    assert readers.decode_attention_roofline(run) == \
        pytest.approx(100 * least / 1.0)
    assert readers.step_mfu(run) == pytest.approx(
        100 * flops.decode_step_flops(MIXTRAL, [100, 50]) / (10 * 197e12))
    assert readers.decode_step_ms(run) == pytest.approx(100.0)
    # nothing to read: no chunk calls, no flash kernel, no recovery
    assert readers.flash_attention_roofline(run) is None
    assert readers.prefill_chunk_ms(run) is None
    assert readers.recovery_tick_ms(run) is None
    assert readers.queue_wait_p50_s(run) is None


def test_a_missing_kernel_reads_nothing_not_zero():
    from test_bench_flops import MIXTRAL
    tr = synthetic()
    tr.devices[DEV] = [e for e in tr.devices[DEV]
                       if not e.name.startswith("moe_gemm")]
    run = run_of(tr, {"decode": FakeProbe([(0, 1, {"ctx": [10]})])}, MIXTRAL)
    assert readers.moe_gemm_roofline(run) is None


def test_load_reads_host_spans_of_a_cpu_trace():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("harness.engine_step"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = trace.xplane_path(d)
        assert os.path.getsize(path) > 0
        tr = trace.load(path)
    names = {s.name for s in tr.spans}
    assert {"harness.window", "harness.engine_step"} <= names
    assert tr.window_s > 0
    # the CPU has no TPU plane: nothing is read as device time
    assert tr.devices == {} and trace.busy_seconds(tr) == 0.0


def test_a_device_trace_cut_short_is_found():
    ops = [Event("moe_gemm.1", 1.0, 2.0), Event("fusion.2", 4.0, 5.0)]
    calls = [Event("harness.window", 0.0, 10.0),
             Event("jit.decode", 0.9, 2.1), Event("jit.decode", 3.9, 5.1)]
    tr = Trace({DEV: ops}, list(calls), 0.0, 10.0)
    assert trace.lost_device_ops(tr) is None
    # two more engine calls, and no device op after the first two
    tr.spans += [Event("jit.decode", 6.0, 6.9), Event("jit.decode", 8.0, 9.0)]
    assert trace.lost_device_ops(tr) == 6.0
    # a call that begins after the window is not the window's
    late = Trace({DEV: ops}, calls + [Event("jit.decode", 11.0, 12.0)],
                 0.0, 10.0)
    assert trace.lost_device_ops(late) is None
