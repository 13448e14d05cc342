"""Each configuration of BENCHMARK.json against its source's published
config, ``bench/configs/published/<name>.json`` (every key of the source's
config.json that fixes a size or an equation), and the mapping of its keys
onto the program's ModelConfig by its family."""
import json

import pytest
from bench_tiny import BENCH, ROOT

from harness import spec, weights

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {e["name"]: e for e in BENCHMARK["configs"]}
# the one size a cut may change: depth (a published width, expert count or
# vocabulary is never reduced)
DEPTH = "L"


def load(name):
    return json.loads((ROOT / CONFIGS[name]["file"]).read_text())


def published(name):
    return json.loads(
        (BENCH / "configs" / "published" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_only_the_listed_keys_differ_from_the_source(name):
    c, pub = load(name), published(name)
    changed = {k for k, v in pub.items() if c[k] != v}
    assert changed == set(c["reduced"]) == set(CONFIGS[name]["reduced"])
    assert changed <= {spec.family(c).KEYS[DEPTH]}
    assert CONFIGS[name]["source"] == c["source"] == pub["source"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_model_config_keeps_every_width(name):
    c, pub = load(name), published(name)
    m = spec.family(c).model_config(c, name)
    d = weights.dims(c)
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.head_dim_) == \
        (d["D"], d["H"], d["Hkv"], d["Dh"])
    assert (m.moe.num_experts, m.moe.top_k, m.moe.d_ff) == \
        (d["E"], d["K"], d["F"])
    assert m.vocab_size == d["V"] and m.num_layers == d["L"]
    assert m.norm_eps == pub["rms_norm_eps"]
    assert m.rope_theta == pub["rope_theta"]
    assert m.act == pub["hidden_act"]
    assert m.tie_embeddings == pub["tie_word_embeddings"]
    assert m.dtype == pub["torch_dtype"]
    # no source config states a per-head q/k norm: the file says it
    assert m.qk_norm == c["qk_norm"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_widths_are_the_published_ones(name):
    c, pub = load(name), published(name)
    keys = spec.family(c).KEYS
    d = weights.dims(c)
    for short, key in keys.items():
        if short == DEPTH and key in c["reduced"]:
            continue
        if short == "Dh" and key not in pub:
            # a config.json without head_dim means hidden_size / heads
            want = pub[keys["D"]] // pub[keys["H"]]
        else:
            want = pub[key]
        assert d[short] == want, short


def test_refuses_what_the_program_cannot_compute():
    c = load("mixtral-8x7b-2l")
    fam = spec.family(c)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        fam.model_config(dict(c, norm_topk_prob=False), "m")
    with pytest.raises(ValueError, match="q/k norm"):
        fam.arch(dict(c, qk_norm=True))


def test_a_configuration_without_a_family_is_refused():
    c = load("mixtral-8x7b-2l")
    del c["family"]
    with pytest.raises(ValueError, match="family"):
        weights.dims(c)
    with pytest.raises(KeyError, match="no family"):
        weights.dims(dict(c, family="no-such-family"))


def test_weight_tree_shares_the_arrays():
    from bench_tiny import small_cell
    c = small_cell("mixtral-8x7b-2l.chat").config
    w = weights.make_weights(c, 7)
    p = spec.family(c).program_params(w, c)
    assert p["blocks"][0]["moe"]["experts"]["wg"] is w["layers"]["w_gate"]
    assert p["blocks"][0]["attn"]["wq"] is w["layers"]["wq"]
    assert w["layers"]["w_gate"].dtype.name == "bfloat16"
