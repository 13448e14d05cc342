"""The Qwen3 MoE family: its config keys, its weight layout, the program's
tree for those weights, and a plain forward pass from the published
equations, in float32.

The harness finds this module by a configuration file's ``"family":
"qwen3"`` (``harness.spec.family``). What Qwen3's MoE block shares with
Mixtral's -- RMSNorm, rotate-half RoPE, grouped-query causal attention, a
softmax router whose top-K weights are renormalised (``norm_topk_prob``),
SwiGLU experts, an untied head -- it takes from ``mixtral.py``; it adds the
per-head q/k norm and reads Qwen3's key names.

Qwen3-30B-A3B (Qwen3 Technical Report, arXiv:2505.09388;
huggingface.co/Qwen/Qwen3-30B-A3B ``config.json``, ``Qwen3MoeAttention``),
with x the residual stream of one sequence:

    q, k, v = RMSNorm(x) Wq, Wk, Wv
    q_h = RoPE(RMSNorm_qnorm(q_h)),  k_h = RoPE(RMSNorm_knorm(k_h))
        each head h normalised over its own head_dim, with a scale of
        head_dim shared by the heads and rms_norm_eps, before RoPE
    h = x + Wo . Attn(q, k, v)        (GQA, causal, 1 / sqrt(Dh))
    y = h + the MoE of mixtral.py over ``num_experts`` experts of width
        ``moe_intermediate_size``, top ``num_experts_per_tok``
    logits = RMSNorm(y_L) . unembed^T

Every layer is an MoE layer (``decoder_sparse_step`` 1, ``mlp_only_layers``
[]); ``intermediate_size`` is a dense width the model never uses. As in
``mixtral.py``: one sequence at a time, one layer per jitted call, every
expert evaluated densely, ``mode`` "f32" (the reference) or "fp8" (the
control).
"""
from __future__ import annotations

from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_family

mixtral = load_family("mixtral", Path(__file__).resolve().parent)

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
    """The program's ``ModelConfig`` of configuration file ``c``. Refuses
    what the program cannot compute as published."""
    if not c["qk_norm"]:
        raise ValueError(f"{name}: Qwen3's attention has a per-head q/k "
                         "norm")
    if c.get("decoder_sparse_step", 1) != 1 or c.get("mlp_only_layers"):
        raise ValueError(f"{name}: the program serves a stack in which "
                         "every layer is an MoE layer")
    from repro.launch import serve
    if not hasattr(serve, "dropless_capacity_factor"):
        raise ValueError(
            f"{name}: the program's build_server fixes the capacity factor "
            f"at 4.0, which drops routes at {c[KEYS['E']]} experts over "
            f"top {c[KEYS['K']]}; this family is served dropless")
    return mixtral.model_config(_as_mixtral(c), name)


def program_params(w: dict, c: dict):
    out = mixtral.program_params(w, c)
    attn = out["blocks"][0]["attn"]
    attn["q_norm"] = {"scale": w["layers"]["q_norm"]}
    attn["k_norm"] = {"scale": w["layers"]["k_norm"]}
    return out


class Arch(NamedTuple):
    """The scalars of a configuration the equations use; ``qk_norm`` is
    always True for this family (tests turn it off to show it matters)."""
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


def project(u, lw, a: Arch, pos, mode: str):
    """q [S, H, Dh], k and v [S, Hkv, Dh] of the normed stream ``u`` at
    positions ``pos``: q and k normalised per head, then rotated."""
    mm, rmsnorm, rope = mixtral.mm, mixtral.rmsnorm, mixtral.rope
    s = u.shape[0]
    q = mm(u, lw["wq"], mode).reshape(s, a.heads, a.head_dim)
    k = mm(u, lw["wk"], mode).reshape(s, a.kv_heads, a.head_dim)
    v = mm(u, lw["wv"], mode).reshape(s, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = rmsnorm(q, lw["q_norm"], a.eps)
        k = rmsnorm(k, lw["k_norm"], a.eps)
    return rope(q, pos, a.theta), rope(k, pos, a.theta), v


def attention(x, lw, a: Arch, mode: str):
    mm = mixtral.mm
    s = x.shape[0]
    pos = jnp.arange(s)
    q, k, v = project(mixtral.rmsnorm(x, lw["ln1"], a.eps), lw, a, pos,
                      mode)
    g = a.heads // a.kv_heads
    causal = pos[:, None] >= pos[None, :]

    def one_group(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * g, g, axis=1)   # [S, g, Dh]
        ki, vi = k[:, i], v[:, i]                                # [S, Dh]
        sc = mm(qi.transpose(1, 0, 2), ki.T, mode) / np.sqrt(a.head_dim)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm(p, vi, mode)                                   # [g, S, Dh]

    o = jax.lax.map(one_group, jnp.arange(a.kv_heads))       # [Hkv, g, S, Dh]
    o = o.reshape(a.heads, s, a.head_dim).transpose(1, 0, 2).reshape(s, -1)
    return x + mm(o, lw["wo"], mode)


def _layer(x, lw, a: Arch, mode: str):
    with jax.default_matmul_precision("highest"):
        return mixtral.moe(attention(x, lw, a, mode), lw, a, mode)


_layer_jit = jax.jit(_layer, static_argnames=("a", "mode"))


def logits(w: dict, c: dict, tokens: np.ndarray, at: np.ndarray,
           mode: str = "f32"):
    """Logits [len(at), V] of one sequence ``tokens`` at positions ``at``.
    Padding at the end cannot reach earlier positions (causal)."""
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
