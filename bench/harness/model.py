"""The system under test, built from a configuration file.

The serving stack is the launcher's own: ``repro.launch.serve.
build_server``. This module only translates the configuration file into the
program's ``ModelConfig`` (by its family's ``model_config``) and
``EngineConfig``, and hands the program the benchmark's weights
(``harness.weights``) in the program's own tree (by its family's
``program_params``).
"""
from __future__ import annotations

import gc

import jax

from harness.spec import family


def engine_config(c: dict):
    from repro.serving.engine import EngineConfig
    e = c["engine"]
    return EngineConfig(
        max_batch=e["max_batch"], max_seq=e["max_seq"], num_aw=e["num_aw"],
        num_ew=e["num_ew"], kv_page_tokens=e["kv_page_tokens"],
        chunk_token_budget=e["chunk_token_budget"], chunk_min=e["chunk_min"])


def build(c: dict, name: str, seed: int, make_weights):
    """(engine, orchestrator, weights): the served stack with the
    benchmark's weights installed. ``make_weights()`` is called once the
    program's own initial weights are freed, so two trees never share the
    chip."""
    from repro.launch.serve import build_server
    fam = family(c)
    cfg = fam.model_config(c, name)
    eng, orch = build_server(cfg, engine_config(c), seed=seed & 0x7FFFFFFF)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), eng.params)
    eng.params = None
    gc.collect()
    w = make_weights()
    params = fam.program_params(w, c)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise ValueError(f"{name}: the benchmark's weights do not match the "
                         f"program's tree:\n got {got}\nwant {want}")
    eng.params = params
    return eng, orch, w
