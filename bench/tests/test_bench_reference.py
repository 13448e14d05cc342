"""The float32 reference against the served path, and the check that
decides ``correct``, at small widths of the configurations on the CPU.

The served path is the benchmark's own: chunked prefill through the paged
cache, then paged decode, driven by the harness's loop. A backlog due at
once makes the run independent of the host's speed, so the numbers below
repeat exactly. The program serves these small cells in float32, so it and
the reference differ only in the order of their sums: any other
disagreement is a fault of one of them."""
import copy
import time

import jax.numpy as jnp
import pytest
from bench_tiny import SMALL_LIMIT, paced, run_small, small_cell

from harness import check, loop, model, session, traffic
from harness.weights import dims, make_weights

CHAT, FAILOVER = "mixtral-8x7b-2l.chat", "mixtral-8x7b-2l.failover"


def backlog(cell, n=6):
    mix = copy.deepcopy(cell.traffic)
    mix["arrivals"] = {"kind": "backlog", "requests": n}
    return mix


def f32(cell):
    cell = copy.deepcopy(cell)
    cell.config["torch_dtype"] = "float32"
    return cell


def drain(cell, seed=3, fault=None):
    """Serve a backlog of the cell's mix to the end; returns (weights,
    served, routes the program dropped)."""
    c, d = cell.config, dims(cell.config)
    eng, orch, w = model.build(c, cell.config_name, seed,
                               lambda: make_weights(c, seed))
    if fault is not None:
        fault(eng)
    count = session.DispatchCount(eng)
    reqs = traffic.generate(backlog(cell), seed, 1.0, d["V"])
    s = loop.serve(eng, orch, reqs, [], float("inf"),
                     stop=loop.drained(eng, orch))
    routed = d["K"] * d["L"] * (
        s.prefill_tokens + sum(len(v) for v in s.stamps.values()))
    return w, s, reqs, routed - count.total


@pytest.fixture(scope="module")
def served():
    cell = f32(small_cell(CHAT))
    c = cell.config
    w, s, reqs, dropped = drain(cell)
    assert dropped == 0
    assert sorted(s.outputs) == sorted(r.rid for r in reqs)
    assert all(len(s.outputs[r.rid]) == r.max_new for r in reqs)
    # every request went through more than one prefill chunk
    assert max(len(r.prompt) for r in reqs) > c["engine"]["chunk_min"]
    return cell, w, s


def test_served_tokens_agree_with_the_reference(served):
    cell, w, s = served
    got = check.compare(w, cell.config, s, sorted(s.outputs))
    assert got["tokens"] >= 40
    assert got["mismatch_share"] <= 0.02
    assert got["max_gap"] < 1e-3 and got["mean_gap"] < 1e-4
    assert got["clipped_mean_gap"] <= got["mean_gap"]


def test_float8_control_is_not_correct(served):
    cell, w, s = served
    ctl = check.compare(w, cell.config, s, sorted(s.outputs), control=True)
    assert ctl["clipped_mean_gap"] > SMALL_LIMIT


def test_sample_keeps_the_longest_and_struck(served):
    cell, w, s = served
    longest = max(s.outputs, key=lambda r: s.prompt_len[r] +
                  len(s.outputs[r]))
    rids = check.sample(s, 9, struck={"r00002"}, min_tokens=10 ** 9)
    assert rids[0] == longest and "r00002" in rids
    assert sorted(rids) == sorted(s.outputs)


def test_a_sound_run_is_correct():
    out = run_small(f32(small_cell(CHAT)), 4, seconds=2.0)
    assert out["correct"] is True
    assert out["compared"]["clipped_mean_gap"]["value"] <= SMALL_LIMIT
    assert set(out["metrics"]) == {"ttft_p50_s", "tbt_p99_ms", "setup_s"}
    assert out["attempted"] > 0


def alter_tokens(eng):
    """The fault: every token the sampler produces is replaced by the
    next id, where it is produced."""
    sample, vocab = eng.decode_plane.sample, eng.cfg.vocab_size

    def altered(logits, pos):
        return (sample(logits, pos) + 1) % jnp.int32(vocab)
    eng.decode_plane.sample = altered


def test_altered_tokens_are_not_correct():
    out = run_small(f32(small_cell(CHAT)), 4, seconds=2.0,
                    fault=alter_tokens)
    assert out["correct"] is False
    assert out["compared"]["clipped_mean_gap"]["value"] > SMALL_LIMIT


def one_slot_per_expert(eng):
    """The fault: every prefill chunk gets an expert capacity of one row,
    so an expert that draws two of a chunk's tokens drops one."""
    eng.prefill_capacity = lambda n_real_tokens: 1


def test_dropped_routes_are_counted():
    """A chunk call that drops routes for want of expert capacity leaves
    the count of routes dispatched short of those routed."""
    _, _, _, dropped = drain(f32(small_cell(CHAT)), fault=one_slot_per_expert)
    assert dropped > 0


def test_dropped_routes_are_not_correct():
    out = run_small(f32(small_cell(CHAT)), 4, seconds=2.0,
                    fault=one_slot_per_expert)
    assert out["correct"] is False
    assert out["compared"]["dropped_expert_routes"]["value"] > 0


def test_a_failover_run_is_correct():
    """The failover mix at small widths: its failures strike inside the
    window, the struck requests are in the sample, and what the restored
    requests and the shadow experts served agrees with the reference."""
    out = run_small(f32(small_cell(FAILOVER)), 6, seconds=3.0, fault=paced)
    assert out["correct"] is True
    assert out["compared"]["clipped_mean_gap"]["value"] <= SMALL_LIMIT
    assert set(out["metrics"]) == {"failure_stall_s", "setup_s"}


def test_requests_due_while_the_loop_is_held_are_attempted():
    """A tick that holds the loop past the window's end, as a slow restore
    does: the requests that fell due meanwhile never reach the gateway,
    and still count as attempted, with no token."""
    cell = f32(small_cell(CHAT))
    c = cell.config
    eng, orch, _ = model.build(c, cell.config_name, 3,
                               lambda: make_weights(c, 3))
    reqs = traffic.generate(cell.traffic, 3, 2.0, dims(c)["V"])
    tick = orch.tick

    def held(now):
        if now < 0.1:
            time.sleep(2.5)
        return tick(now)
    orch.tick = held
    s = loop.serve(eng, orch, reqs, [], 2.0)
    assert s.end >= 2.5
    assert sorted(s.due) == sorted(r.rid for r in reqs if r.due < s.end)
    assert len(s.due) > 1 and not s.stamps


def test_a_traced_run_reads_per_layer_metrics():
    out = run_small(f32(small_cell(CHAT)), 5, seconds=2.0, traced=True)
    assert out["correct"] is True
    # the CPU has no device plane: only the host's readings come back
    assert {"decode_step_ms.chat", "step_mfu.chat", "queue_wait_p50_s"} <= \
        set(out["metrics"])
    assert "device_idle.chat" not in out["metrics"]
    assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "compared"
