"""The harness off the chip: the command refuses anything but a TPU, the
benchmark file is well formed and finds every piece by name, and the
traffic generator gives every seed the same work."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from bench_tiny import BENCH, ROOT

from harness import spec, traffic

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "4294967311",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_a_tpu():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_piece_is_found_by_name():
    for w in BENCHMARK["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m.name for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m.name))
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_benchmark_file_is_well_formed():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    assert all(w["chips"] == 1 for w in b["workloads"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for x in b["configs"] + b["workloads"]:
        assert 1 <= len(x["why"]) <= 200


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")


def mix(kind="poisson"):
    t = json.loads((BENCH / "traffic" / "chat.json").read_text())
    if kind == "backlog":
        t["arrivals"] = {"kind": "backlog", "requests": 20}
    return t


@pytest.mark.parametrize("kind", ["poisson", "backlog"])
def test_every_seed_serves_the_same_schedule(kind):
    a = traffic.generate(mix(kind), 5, 30.0, 32000)
    b = traffic.generate(mix(kind), 2 ** 33 + 5, 30.0, 32000)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    again = traffic.generate(mix(kind), 5, 30.0, 32000)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               for x, y in zip(a, again))


def test_poisson_mix_spans_the_window():
    t = mix()
    reqs = traffic.generate(t, 1, 40.0, 32000)
    assert len(reqs) == round(t["arrivals"]["rate_per_s"] * 40.0)
    assert 0 < reqs[0].due <= reqs[-1].due <= 40.0 * 1.5
    p = t["prompt_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32000 for r in reqs)


@pytest.mark.parametrize("keeps_up", [True, False])
def test_sweep_judges_a_rate_by_its_second_half(keeps_up):
    """A request due every 5 s over 100 s, each served in 4 s: sustained.
    Where the second half's requests never finish, the outstanding count
    grows and the rate is not sustained."""
    import sweep
    from harness.loop import Served
    s = Served()
    for i in range(20):
        s.due[f"r{i}"] = 5.0 * i
        if keeps_up or i < 10:
            s.finished[f"r{i}"] = 5.0 * i + 4.0
    s.end = 100.0
    row = sweep.judge(s, 0.2, 100.0)
    assert row["sustained"] is keeps_up
    assert row["due_per_s_2nd_half"] == pytest.approx(0.2)
    assert row["outstanding_at_end"] == (0 if keeps_up else 10)
