"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it served -- drawn
from the seed, always with the longest one, with the requests a failure
struck, and grown to some hundreds of served tokens -- is run through the
float32 reference of the configuration's family (``logits`` of
``bench/reference/<family>.py``) over prompt + served tokens. At each
position where a token was served, the gap is how far the served token's
reference logit lies below the reference's best logit (0 where they
agree). The number compared is the clipped mean gap: the mean
over the sample's served positions of the gap, each cut off at ``CLIP``
logits. A router near-tie now and then sends a sound bfloat16 token to
another expert than float32 does, and such a position reads a gap of up
to a logit and more: unclipped, one or two of them carry the whole mean
of a sample of 200-400 tokens and swing it from seed to seed and run to
run, while the cut-off leaves each of them weighing as one clear miss.
The float8 control misses at many positions, and a token altered where it
is produced misses at every one, so both stay far above sound runs. The
plain mean and the widest gap are printed, not compared. The control
reads the gap of the token a float8 copy of the reference puts first at
the same positions.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np

from harness.loop import Served
from harness.spec import family

MIN_TOKENS = 300     # served tokens the sample grows to
MAX_STRUCK = 6       # struck requests kept in the sample
CLIP = 0.25          # logits: the most one position adds to the clipped mean


def sample(served: Served, seed: int, struck: Iterable[str] = (),
           min_tokens: int = MIN_TOKENS) -> List[str]:
    """Requests to check: the longest (prompt + served tokens), those a
    failure struck (at most MAX_STRUCK), then others drawn from the seed
    until the sample holds ``min_tokens`` served tokens. A request still in
    flight when the window closed counts with the tokens it had."""
    toks = served.tokens()
    done = sorted(toks)
    if not done:
        return []
    longest = max(done, key=lambda r: (served.prompt_len[r] + len(toks[r]),
                                       r))
    rng = np.random.default_rng([seed, 2])
    hit = [str(r) for r in rng.permutation(sorted(set(struck) & set(done)))]
    chosen = [longest] + [r for r in hit[:MAX_STRUCK] if r != longest]
    for r in rng.permutation(done):
        if sum(len(toks[x]) for x in chosen) >= min_tokens:
            break
        if r not in chosen:
            chosen.append(str(r))
    return chosen


def position_gaps(w: dict, c: dict, served: Served, rids: List[str],
                  control: bool = False) -> Dict[str, dict]:
    """Per sampled request, at each served position: ``gap``, how far the
    served token (with ``control``, the float8 reference's first choice)
    lies below the float32 reference's best logit, and ``miss``, whether it
    is not that best."""
    logits = family(c).logits
    served_tokens = served.tokens()
    out = {}
    for rid in rids:
        prompt, toks = served.prompts[rid], served_tokens[rid]
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        ref = np.asarray(logits(w, c, seq, at, "f32"), np.float64)
        if control:
            pick = np.asarray(logits(w, c, seq, at, "fp8")).argmax(-1)
        else:
            pick = np.asarray(toks)
        out[rid] = {"gap": ref.max(-1) - ref[np.arange(len(toks)), pick],
                    "miss": pick != ref.argmax(-1)}
    return out


def compare(w: dict, c: dict, served: Served, rids: List[str],
            control: bool = False) -> Dict[str, float]:
    """Over the sampled requests' served positions: ``tokens`` compared,
    ``mismatch_share`` of them that are not the float32 reference's best,
    the ``max_gap`` and ``mean_gap`` by which they lie below it, and the
    ``clipped_mean_gap``, each position's gap cut off at ``CLIP``. With
    ``control``, the same for the tokens the float8 reference puts first."""
    return summarise(position_gaps(w, c, served, rids, control))


def summarise(per: Dict[str, dict]) -> Dict[str, float]:
    """``compare``'s numbers from ``position_gaps``."""
    g = np.concatenate([p["gap"] for p in per.values()] or [np.zeros(0)])
    miss = int(sum(p["miss"].sum() for p in per.values()))
    return {"tokens": int(g.size),
            "mismatch_share": miss / g.size if g.size else float("nan"),
            "max_gap": float(g.max()) if g.size else float("nan"),
            "mean_gap": float(g.mean()) if g.size else float("nan"),
            "clipped_mean_gap": float(np.minimum(g, CLIP).mean())
            if g.size else float("nan")}
