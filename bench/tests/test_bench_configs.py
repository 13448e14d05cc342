"""Each configuration file against its published config, and the mapping
of its keys onto the program's ModelConfig."""
import json

import pytest
from bench_tiny import BENCH, ROOT

from harness import model, weights

# the published config.json of each model (huggingface.co/<repo>), every
# key that fixes a size or an equation
PUBLISHED = {
    "mixtral-8x7b-2l": {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_hidden_layers": 32, "num_local_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 32000, "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "tie_word_embeddings": False, "hidden_act": "silu",
        "max_position_embeddings": 32768, "sliding_window": None,
        "torch_dtype": "bfloat16"},
}
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def load(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_only_the_listed_keys_differ_from_the_source(name):
    c = load(name)
    changed = {k for k, v in PUBLISHED[name].items() if c[k] != v}
    assert changed == set(c["reduced"]) == {"num_hidden_layers"}
    for entry in BENCHMARK["configs"]:
        if entry["name"] == name:
            assert set(entry["reduced"]) == changed
            assert entry["source"] == c["source"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_model_config_keeps_every_width(name):
    c = load(name)
    m = model.model_config(c, name)
    d = weights.dims(c)
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.head_dim_) == \
        (d["D"], d["H"], d["Hkv"], d["Dh"])
    assert (m.moe.num_experts, m.moe.top_k, m.moe.d_ff) == \
        (d["E"], d["K"], d["F"])
    assert m.vocab_size == d["V"] and m.num_layers == d["L"]
    assert m.norm_eps == c["rms_norm_eps"] and m.rope_theta == 1e6
    assert not m.qk_norm
    assert not m.tie_embeddings and m.dtype == "bfloat16"


def test_widths_are_the_published_ones():
    for name in PUBLISHED:
        d = weights.dims(load(name))
        assert (d["D"], d["F"], d["E"], d["K"], d["Dh"]) == \
            (4096, 14336, 8, 2, 128)


def test_refuses_what_the_program_cannot_compute():
    from reference.model import arch
    c = dict(load("mixtral-8x7b-2l"), norm_topk_prob=False)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        model.model_config(c, "m")
    with pytest.raises(ValueError, match="q/k norm"):
        arch(dict(load("mixtral-8x7b-2l"), qk_norm=True))


def test_weight_tree_shares_the_arrays():
    from bench_tiny import small_cell
    c = small_cell("mixtral-8x7b-2l.chat").config
    w = weights.make_weights(c, 7)
    p = model.program_params(w, c)
    assert p["blocks"][0]["moe"]["experts"]["wg"] is w["layers"]["w_gate"]
    assert p["blocks"][0]["attn"]["wq"] is w["layers"]["wq"]
    assert w["layers"]["w_gate"].dtype.name == "bfloat16"
