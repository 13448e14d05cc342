"""Find a cell's knee: the highest arrival rate its traffic mix sustains.

    python bench/sweep.py --workload <cell> --rates 0.6,0.9,1.2 --seconds 120

One process, one set-up; each rate serves the mix for ``--seconds`` and
prints one JSON line. A window starts from an empty engine, so the numbers
that judge it come from its second half, once the first requests have had
a lifetime to finish: requests due and finished per second there, and the
requests outstanding (due, not finished) at the half and at the end. The
system sustains a rate when it finishes about what arrives in the second
half and the outstanding count does not grow. Give a window several of the
longest request lifetimes: the chat mix's longest requests (384 answer
tokens at some 0.25 s a tick) live about 100 s, so a 120-s window from
empty is still filling when it closes and this test reads it as not
sustained. Between rates the engine drains; the sweep stops after two
rates in a row that are not sustained.

Not part of a benchmark run: the knee is found once, and the cell's traffic
file holds a fixed rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

import run as bench_run


def outstanding(s, t: float) -> int:
    """Requests due by ``t`` and not finished by ``t``."""
    return sum(1 for r, d in s.due.items()
               if d <= t and s.finished.get(r, float("inf")) > t)


def judge(s, rate: float, seconds: float) -> dict:
    half = seconds / 2
    ttft = [(st[0] if st else s.end) - s.due[r]
            for r, st in ((r, s.stamps.get(r)) for r in s.due)]
    late = [r for r, d in s.due.items() if d >= half]
    fin2 = sum(1 for t in s.finished.values() if t >= half)
    out_half, out_end = outstanding(s, half), outstanding(s, s.end)
    life = [s.finished[r] - s.due[r] for r in s.finished]
    return {
        "rate_per_s": rate, "due": len(s.due), "finished": len(s.finished),
        "due_per_s_2nd_half": len(late) / (s.end - half),
        "finished_per_s_2nd_half": fin2 / (s.end - half),
        "outstanding_at_half": out_half, "outstanding_at_end": out_end,
        "sustained": fin2 >= 0.9 * len(late) and out_end <= out_half + 2,
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p90_s": float(np.percentile(ttft, 90)),
        "lifetime_p50_s": float(np.median(life)) if life else None,
        "tokens_per_s": (s.prefill_tokens + sum(
            len(v) for v in s.stamps.values())) / s.end}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from harness import loop, session, spec, traffic
    from harness.weights import dims
    cell = spec.load_cell(args.workload)
    import jax
    bench_run.use_checkout_cache(jax)
    try:
        bench_run.check_device(jax, cell.chips)
    except bench_run.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    counter = session.CompileCounter()
    t_start = time.monotonic()
    eng, orch, _ = session.setup(cell, args.seed, t_start, counter)
    print(json.dumps({"engine": cell.config["engine"],
                      "setup_s": time.monotonic() - t_start}), flush=True)
    vocab = dims(cell.config)["V"]
    missed = 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        reqs = traffic.generate(mix, args.seed + i, args.seconds, vocab)
        for r in reqs:
            r.rid = f"s{i}-{r.rid}"
        s = loop.serve(eng, orch, reqs, [], args.seconds)
        row = judge(s, rate, args.seconds)
        stats = jax.devices()[0].memory_stats() or {}
        row["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        print(json.dumps(row), flush=True)
        missed = 0 if row["sustained"] else missed + 1
        if missed >= 2:
            break
        loop.serve(eng, orch, [], [], float("inf"),
                   stop=loop.drained(eng, orch))
    return 0


if __name__ == "__main__":
    sys.exit(main())
