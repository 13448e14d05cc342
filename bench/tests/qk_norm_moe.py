"""A family for the tests alone: Mixtral's equations plus a per-head RMSNorm
on q and on k before RoPE (as Qwen3's MoE models have), under Qwen3's
config key names: ``num_experts``, ``moe_intermediate_size``, and an
``intermediate_size`` that the model never uses.

The harness finds it as it finds ``bench/reference/<family>.py``, once a
test points ``harness.spec.REFERENCE_DIR`` here. Nothing under
``bench/harness`` names it: an architecture joins the benchmark by new
files alone. What it shares with Mixtral it takes from
``bench/reference/mixtral.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import BENCH_DIR, load_family

mixtral = load_family("mixtral", BENCH_DIR / "reference")

KEYS = dict(mixtral.KEYS, E="num_experts", F="moe_intermediate_size")


def _as_mixtral(c: dict) -> dict:
    """``c`` under Mixtral's key names, for what the two families share."""
    return dict(c, **{mixtral.KEYS[k]: c[key] for k, key in KEYS.items()})


def weight_shapes(c: dict) -> dict:
    """Mixtral's leaves, and the q and k norm scales [L, Dh]."""
    out = mixtral.weight_shapes(_as_mixtral(c))
    L, Dh = c[KEYS["L"]], c[KEYS["Dh"]]
    out["layers"].update(q_norm=(L, Dh), k_norm=(L, Dh))
    return out


def model_config(c: dict, name: str):
    if not c["qk_norm"]:
        raise ValueError(f"{name}: this family has a per-head q/k norm")
    return mixtral.model_config(_as_mixtral(c), name)


def program_params(w: dict, c: dict):
    out = mixtral.program_params(w, c)
    attn = out["blocks"][0]["attn"]
    attn["q_norm"] = {"scale": w["layers"]["q_norm"]}
    attn["k_norm"] = {"scale": w["layers"]["k_norm"]}
    return out


class Arch(NamedTuple):
    eps: float
    theta: float
    top_k: int
    heads: int
    kv_heads: int
    head_dim: int
    qk_norm: bool


def arch(c: dict) -> Arch:
    return Arch(float(c["rms_norm_eps"]), float(c["rope_theta"]),
                int(c[KEYS["K"]]), int(c[KEYS["H"]]), int(c[KEYS["Hkv"]]),
                int(c[KEYS["Dh"]]), True)


def attention(x, lw, a: Arch, mode: str):
    mm, rmsnorm, rope = mixtral.mm, mixtral.rmsnorm, mixtral.rope
    s = x.shape[0]
    pos = jnp.arange(s)
    u = rmsnorm(x, lw["ln1"], a.eps)
    q = mm(u, lw["wq"], mode).reshape(s, a.heads, a.head_dim)
    k = mm(u, lw["wk"], mode).reshape(s, a.kv_heads, a.head_dim)
    v = mm(u, lw["wv"], mode).reshape(s, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = rmsnorm(q, lw["q_norm"], a.eps)
        k = rmsnorm(k, lw["k_norm"], a.eps)
    q, k = rope(q, pos, a.theta), rope(k, pos, a.theta)
    g = a.heads // a.kv_heads
    causal = pos[:, None] >= pos[None, :]

    def one_group(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * g, g, axis=1)
        ki, vi = k[:, i], v[:, i]
        sc = mm(qi.transpose(1, 0, 2), ki.T, mode) / np.sqrt(a.head_dim)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm(p, vi, mode)

    o = jax.lax.map(one_group, jnp.arange(a.kv_heads))
    o = o.reshape(a.heads, s, a.head_dim).transpose(1, 0, 2).reshape(s, -1)
    return x + mm(o, lw["wo"], mode)


def _layer(x, lw, a: Arch, mode: str):
    with jax.default_matmul_precision("highest"):
        return mixtral.moe(attention(x, lw, a, mode), lw, a, mode)


_layer_jit = jax.jit(_layer, static_argnames=("a", "mode"))


def logits(w: dict, c: dict, tokens: np.ndarray, at: np.ndarray,
           mode: str = "f32"):
    a = arch(c)
    n = len(tokens)
    padded = np.zeros(-(-n // mixtral.PAD) * mixtral.PAD, np.int32)
    padded[:n] = tokens
    x = mixtral._embed(w["embed"], jnp.asarray(padded))
    for i in range(c[KEYS["L"]]):
        lw = {k: v[i] for k, v in w["layers"].items()}
        x = _layer_jit(x, lw, a, mode)
    unembed = w.get("unembed", w["embed"])
    return mixtral._head_jit(x, w["final_norm"], unembed, jnp.asarray(at),
                             a, mode)
