"""Small copies of the benchmark's cells for the CPU tests.

Each keeps its configuration's structure (expert count, top-k, untied head,
norm eps, RoPE theta, engine layout) and shrinks only the widths, depth of
the cache and the traffic, so that a whole run fits in seconds on the CPU.
Widths are set by their short names through the family's ``KEYS``, so a
cell of any family shrinks alike. The chip runs use the files as they
are.
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

SMALL_WIDTHS = {"D": 64, "H": 4, "Hkv": 2, "Dh": 16, "V": 256, "F": 32}
SMALL_ENGINE = {"max_batch": 4, "max_seq": 256, "kv_page_tokens": 32,
                "chunk_token_budget": 32, "chunk_min": 32}
SMALL_LENGTHS = {"prompt_tokens": {"median": 24, "sigma": 0.6, "min": 8,
                                   "max": 64},
                 "output_tokens": {"median": 8, "sigma": 0.5, "min": 3,
                                   "max": 16}}
# clipped mean gap of the small cells on the CPU, served in float32: a
# sound run reads 0 to rounding, the float8 control 0.009 to 0.028 (chat
# backlog, seeds 3-7)
SMALL_LIMIT = 0.005


def small_cell(name: str, experts: int = None) -> spec.Cell:
    """Cell ``name`` (``<config>.<traffic>``) at small widths: the cell of
    BENCHMARK.json where it is one, else built from the two files."""
    try:
        cell = copy.deepcopy(spec.load_cell(name))
    except KeyError:
        config, traffic = name.split(".", 1)
        cell = spec.Cell(name, 1, config, json.loads(
            (BENCH / "configs" / f"{config}.json").read_text()), traffic,
            json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()))
    return shrink(cell, experts)


def shrink(cell: spec.Cell, experts: int = None) -> spec.Cell:
    """``cell`` at small widths, engine and traffic, in place."""
    c = cell.config
    keys = spec.family(c).KEYS
    c.update({keys[k]: v for k, v in SMALL_WIDTHS.items()})
    if experts is not None:
        c[keys["E"]] = experts
    c["engine"].update(SMALL_ENGINE)
    c["check"] = {"clipped_mean_gap": SMALL_LIMIT}
    cell.traffic.update(copy.deepcopy(SMALL_LENGTHS))
    a = cell.traffic["arrivals"]
    if a["kind"] == "poisson":
        a["rate_per_s"] = 6.0
    else:
        a["requests"] = 6
    return cell


def paced(eng, seconds: float = 0.01):
    """Make each engine step last ``seconds`` more. A small cell's step
    takes about a millisecond on a fast CPU, so its short requests can all
    have finished before a failure strikes; paced like this (a chip's step
    takes ~130 ms) some are decoding when one does, on any host."""
    step = eng.step

    def slow(*args, **kwargs):
        time.sleep(seconds)
        return step(*args, **kwargs)
    eng.step = slow


def cpu_device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run_small(cell: spec.Cell, seed: int, seconds: float = 2.0,
              traced: bool = False, fault=None) -> dict:
    from harness import session
    return session.run(cell, seed, seconds, traced,
                       t_process=time.monotonic(), device=cpu_device(),
                       peaks=CPU_PEAKS, fault=fault)
