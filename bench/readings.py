"""The readings a check limit is set from: over several seeds, the mean gap
by which the served tokens lie below the float32 reference's best logit
(sound runs: the lower reading) and the same gap for the tokens the float8
control picks at the same positions (the upper reading). One process, one
window per seed at the cell's own load.

    python bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 20

Prints one JSON line per seed: {"seed", "correct", "compared", "program",
"control"}, the last two with each side's tokens compared, mismatch share,
widest and mean gap. With ``--dump FILE``, appends per seed one JSON line
with every sampled request's due time, token stamps, the failures and whom
they struck, and each served position's gap on both sides: enough to work
out any other number from the same positions.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run
from harness import check


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    from harness import spec
    cell = spec.load_cell(args.workload)
    import jax
    bench_run.use_checkout_cache(jax)
    try:
        device = bench_run.check_device(jax, cell.chips)
    except bench_run.NoChip as e:
        print(f"bench/readings.py: {e}", file=sys.stderr)
        return 2
    peaks = spec.load_peaks(device["kind"])
    from harness import session
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}

        def read(w, c, served, rids, seed=seed):
            got["program"] = check.position_gaps(w, c, served, rids)
            got["control"] = check.position_gaps(w, c, served, rids,
                                                 control=True)
            if args.dump:
                with open(args.dump, "a") as f:
                    f.write(json.dumps(dump(seed, served, rids, got)) + "\n")

        out = session.run(cell, seed, args.seconds, False,
                          t_process=time.monotonic(), device=device,
                          peaks=peaks, on_check=read)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "compared": out["compared"],
                          **{k: check.summarise(v) for k, v in got.items()}
                          }), flush=True)
    return 0


def dump(seed, served, rids, got) -> dict:
    struck = {rid: [round(t, 4) for t, rs in served.struck.items()
                    if rid in rs] for rid in rids}
    return {"seed": seed,
            "failures": [[f.kind, str(f.worker), t]
                         for f, t in served.injected],
            "requests": [{"rid": rid, "prompt_len": served.prompt_len[rid],
                          "due": served.due.get(rid),
                          "stamps": list(served.stamps.get(rid, [])),
                          "struck_at": struck[rid],
                          "gap": got["program"][rid]["gap"].tolist(),
                          "miss": got["program"][rid]["miss"].tolist(),
                          "control_gap": got["control"][rid]["gap"].tolist()}
                         for rid in rids]}


if __name__ == "__main__":
    sys.exit(main())
