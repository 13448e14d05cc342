"""The seam by which a configuration brings its architecture: the family
module named by its ``"family"``.

Mixtral's readings do not move: its weights, reference logits and FLOP
counts (``test_bench_flops.py``) through the family equal what the harness
drew and computed before the seam (``frozen_mixtral.py``). And a family
that no harness file names, ``qk_norm_moe.py`` beside this file, is served
and checked end to end on the CPU."""
import copy
import json
from pathlib import Path

import jax
import numpy as np
import pytest
from bench_tiny import BENCH, SMALL_LIMIT, run_small, shrink, small_cell

import frozen_mixtral
from harness import spec, weights

CHAT = "mixtral-8x7b-2l.chat"
TESTS = Path(__file__).resolve().parent
FIXTURE = "qk_norm_moe"


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


@pytest.mark.parametrize("seed", [7, 2 ** 33 + 7])
def test_mixtral_weights_are_the_frozen_ones(seed):
    c = small_cell(CHAT).config
    assert spec.family(c).weight_shapes(c) == frozen_mixtral.weight_shapes(c)
    new = weights.make_weights(c, seed)
    old = frozen_mixtral.make_weights(c, seed)
    new_leaves, new_tree = jax.tree_util.tree_flatten(new)
    old_leaves, old_tree = jax.tree_util.tree_flatten(old)
    assert new_tree == old_tree
    for a, b in zip(new_leaves, old_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(bits(a), bits(b))


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_mixtral_logits_are_the_frozen_ones(mode):
    c = small_cell(CHAT).config
    w = weights.make_weights(c, 11)
    tokens = np.random.default_rng(11).integers(0, 256, 40, dtype=np.int32)
    at = np.arange(10, 39)
    new = spec.family(c).logits(w, c, tokens, at, mode)
    old = frozen_mixtral.logits(w, c, tokens, at, mode)
    assert np.array_equal(np.asarray(new), np.asarray(old))


@pytest.fixture
def fixture_cell(monkeypatch):
    """The fixture family's small chat cell, served in float32; the
    harness looks for family modules beside this file."""
    monkeypatch.setattr(spec, "REFERENCE_DIR", TESTS)
    config = json.loads((TESTS / f"{FIXTURE}.json").read_text())
    config["torch_dtype"] = "float32"
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    return shrink(spec.Cell(f"{FIXTURE}.chat", 1, FIXTURE, config, "chat",
                            mix))


def test_the_fixture_family_is_served_correct(fixture_cell):
    c = fixture_cell.config
    d = weights.dims(c)
    assert (d["E"], d["K"], d["F"]) == (8, 2, 32)
    assert c["intermediate_size"] != d["F"]
    out = run_small(fixture_cell, 4, seconds=2.0)
    assert out["correct"] is True
    assert out["compared"]["dropped_expert_routes"]["value"] == 0
    assert out["compared"]["clipped_mean_gap"]["value"] <= SMALL_LIMIT


def test_the_fixture_reference_without_its_qk_norm_is_refused(
        fixture_cell, monkeypatch):
    fam = spec.family(fixture_cell.config)
    assert fam.weight_shapes(fixture_cell.config)["layers"]["q_norm"] == \
        (2, 16)
    arch = fam.arch
    monkeypatch.setattr(fam, "arch",
                        lambda c: arch(c)._replace(qk_norm=False))
    out = run_small(copy.deepcopy(fixture_cell), 4, seconds=2.0)
    assert out["correct"] is False
    assert out["compared"]["dropped_expert_routes"]["value"] == 0
    assert out["compared"]["clipped_mean_gap"]["value"] > SMALL_LIMIT


@pytest.mark.parametrize("word", [FIXTURE, "mixtral", "num_local_experts",
                                  "intermediate_size", "from reference",
                                  "import reference"])
def test_no_harness_file_names(word):
    """The harness reads an architecture only through its family module:
    no file of it names a family, a family's config key or a reference
    module."""
    for path in (BENCH / "harness").rglob("*.py"):
        assert word not in path.read_text().lower(), path
