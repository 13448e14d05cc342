"""Chip benchmark of the Tarragon serving path: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine with a TPU. The cell is an
entry of BENCHMARK.json's ``workloads``. Set-up builds the served stack
with ``repro.launch.serve.build_server``, draws the weights on the device
from ``--seed``, and warms every program the cell's traffic uses; then the
window serves the cell's traffic on the wall clock for ``--seconds``. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the harness's own spans. Either way, what the window served is
checked against the float32 reference of the configuration's family
(``bench/reference/<family>.py``, named by the configuration file).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``compared``: each number the check compared, with its
limit. The same comparisons end standard error. Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
# the program under test, and the benchmark's own modules
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]


class NoChip(Exception):
    pass


def use_checkout_cache(jax):
    """Keep every program, however quick to compile, in the checkout's
    cache, also where the machine names another directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(jax, chips: int) -> dict:
    """The device as JAX reports it; raises NoChip off the TPU, with fewer
    chips than the cell asks for, or with the kernels switched to a path
    that is not the compiled Pallas one."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}"
                     f" ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    kernels = os.environ.get("REPRO_KERNELS", "auto")
    if kernels not in ("auto", "pallas"):
        raise NoChip(f"REPRO_KERNELS={kernels!r} would bypass the compiled "
                     "Pallas kernels")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from harness import spec
    cell = spec.load_cell(args.workload)
    import jax
    use_checkout_cache(jax)
    try:
        device = check_device(jax, cell.chips)
        peaks = spec.load_peaks(device["kind"])
    except (NoChip, KeyError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    from harness import session
    session.log(f"[setup] {cell.name}: device {device}, cache {CACHE_DIR}")
    out = session.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, device=device, peaks=peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
