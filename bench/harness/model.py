"""The system under test, built from a configuration file.

The serving stack is the launcher's own: ``repro.launch.serve.
build_server``. This module only translates the configuration file's keys
into the program's ``ModelConfig`` and ``EngineConfig`` and hands the
program the benchmark's weights (``harness.weights``) in the program's own
tree.
"""
from __future__ import annotations

import gc

import jax

from harness.weights import dims

def model_config(c: dict, name: str):
    """``ModelConfig`` of configuration file ``c``. Refuses what the
    program cannot compute as published."""
    from repro.configs.base import ModelConfig, MoEConfig
    d = dims(c)
    if not c.get("norm_topk_prob", True):
        raise ValueError(f"{name}: the program always renormalises the "
                         "top-k gate weights; norm_topk_prob=false is not "
                         "served")
    if c.get("sliding_window") or c.get("attention_bias"):
        raise ValueError(f"{name}: sliding windows and attention biases "
                         "are not configured here")
    return ModelConfig(
        name=name, arch_type="moe", source=c["source"],
        num_layers=d["L"], d_model=d["D"], num_heads=d["H"],
        num_kv_heads=d["Hkv"], head_dim=d["Dh"], d_ff=0,
        vocab_size=d["V"], rope_theta=float(c["rope_theta"]),
        qk_norm=bool(c["qk_norm"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        moe=MoEConfig(num_experts=d["E"], top_k=d["K"], d_ff=d["F"]))


def engine_config(c: dict):
    from repro.serving.engine import EngineConfig
    e = c["engine"]
    return EngineConfig(
        max_batch=e["max_batch"], max_seq=e["max_seq"], num_aw=e["num_aw"],
        num_ew=e["num_ew"], kv_page_tokens=e["kv_page_tokens"],
        chunk_token_budget=e["chunk_token_budget"], chunk_min=e["chunk_min"])


def program_params(w: dict, c: dict):
    """The benchmark's weights in the transformer stack's tree: one scanned
    unit per layer. The arrays are shared, not copied."""
    layers = w["layers"]
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo")}
    block = {
        "ln1": {"scale": layers["ln1"]},
        "attn": attn,
        "ln2": {"scale": layers["ln2"]},
        "moe": {"router": layers["router"],
                "experts": {"wg": layers["w_gate"], "wu": layers["w_up"],
                            "wd": layers["w_down"]}},
    }
    out = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
           "blocks": (block,)}
    if "unembed" in w:
        out["unembed"] = w["unembed"]
    return out


def build(c: dict, name: str, seed: int, make_weights):
    """(engine, orchestrator, weights): the served stack with the
    benchmark's weights installed. ``make_weights()`` is called once the
    program's own initial weights are freed, so two trees never share the
    chip."""
    from repro.launch.serve import build_server
    cfg = model_config(c, name)
    eng, orch = build_server(cfg, engine_config(c), seed=seed & 0x7FFFFFFF)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), eng.params)
    eng.params = None
    gc.collect()
    w = make_weights()
    params = program_params(w, c)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise ValueError(f"{name}: the benchmark's weights do not match the "
                         f"program's tree:\n got {got}\nwant {want}")
    eng.params = params
    return eng, orch, w
