"""The Mixtral family: its config keys, its weight layout, the program's tree
for those weights, and a plain forward pass from the published equations,
in float32.

The harness finds this module by a configuration file's ``"family":
"mixtral"`` (``harness.spec.family``) and reads everything particular to
the architecture from it: ``KEYS`` (the short width names mapped to this
family's config keys), ``weight_shapes``, ``model_config``,
``program_params``, ``arch`` and ``logits``.

Mixtral-8x7B (arXiv:2401.04088, section 2), with x the residual stream of
one sequence:

    h = x + Wo . Attn(RoPE(q), RoPE(k), v),   q, k, v = RMSNorm(x) Wq, Wk, Wv
        grouped-query attention, causal, softmax(q.k / sqrt(Dh));
        RoPE rotates the two halves of each head: theta^(-2i / Dh)
    y = h + sum over the top-K experts e of
            g_e . down_e(silu(u Wg_e) * u Wu_e),
        u = RMSNorm(h), p = softmax(u Wr) over all E experts, g = the top-K
        of p renormalised to sum to 1 (Mixtral's softmax over the top-K
        logits is the same number)
    logits = RMSNorm(y_L) . unembed^T
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale

No cache, no kernels, no batching, no expert capacity: one sequence at a
time, layer by layer, every expert evaluated densely and weighted by g (0
for the experts a token did not choose). Weights are the benchmark's own
(``harness.weights``), read in their served dtype and widened.

``mode`` "f32" multiplies in float32 at the highest precision: the
reference. "fp8" rounds both operands of every product to float8 (e4m3,
one scale per tensor) first: the control, a step below the bfloat16 the
configurations serve.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0

# short width names -> this family's config keys (``harness.weights.dims``)
KEYS = {"L": "num_hidden_layers", "D": "hidden_size",
        "H": "num_attention_heads", "Hkv": "num_key_value_heads",
        "Dh": "head_dim", "V": "vocab_size", "E": "num_local_experts",
        "K": "num_experts_per_tok", "F": "intermediate_size"}


def weight_shapes(c: dict) -> dict:
    """Every weight of configuration ``c``, stacked over layers:

        embed [V, D]   unembed [V, D] (untied only)   final_norm [D]
        layers: ln1, ln2 [L, D]; wq [L, D, H*Dh]; wk, wv [L, D, Hkv*Dh];
                wo [L, H*Dh, D];
                router [L, D, E]; w_gate, w_up [L, E, D, F];
                w_down [L, E, F, D]
    """
    L, D, H, Hkv, Dh, V, E, F = (c[KEYS[k]] for k in
                                 ("L", "D", "H", "Hkv", "Dh", "V", "E", "F"))
    layers = {
        "ln1": (L, D), "ln2": (L, D),
        "wq": (L, D, H * Dh), "wk": (L, D, Hkv * Dh), "wv": (L, D, Hkv * Dh),
        "wo": (L, H * Dh, D),
        "router": (L, D, E),
        "w_gate": (L, E, D, F), "w_up": (L, E, D, F), "w_down": (L, E, F, D),
    }
    out = {"embed": (V, D), "final_norm": (D,), "layers": layers}
    if not c["tie_word_embeddings"]:
        out["unembed"] = (V, D)
    return out


def model_config(c: dict, name: str):
    """The program's ``ModelConfig`` of configuration file ``c``. Refuses
    what the program cannot compute as published."""
    from repro.configs.base import ModelConfig, MoEConfig
    if not c.get("norm_topk_prob", True):
        raise ValueError(f"{name}: the program always renormalises the "
                         "top-k gate weights; norm_topk_prob=false is not "
                         "served")
    if c.get("sliding_window") or c.get("attention_bias"):
        raise ValueError(f"{name}: sliding windows and attention biases "
                         "are not configured here")
    return ModelConfig(
        name=name, arch_type="moe", source=c["source"],
        num_layers=c[KEYS["L"]], d_model=c[KEYS["D"]],
        num_heads=c[KEYS["H"]], num_kv_heads=c[KEYS["Hkv"]],
        head_dim=c[KEYS["Dh"]], d_ff=0, vocab_size=c[KEYS["V"]],
        rope_theta=float(c["rope_theta"]),
        qk_norm=bool(c["qk_norm"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        moe=MoEConfig(num_experts=c[KEYS["E"]], top_k=c[KEYS["K"]],
                      d_ff=c[KEYS["F"]]))


def program_params(w: dict, c: dict):
    """The weights ``w`` in the transformer stack's tree: one scanned unit
    per layer. The arrays are shared, not copied."""
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


class Arch(NamedTuple):
    """The scalars of a configuration the equations use."""
    eps: float
    theta: float
    top_k: int
    heads: int
    kv_heads: int
    head_dim: int


def arch(c: dict) -> Arch:
    if c["qk_norm"]:
        raise ValueError("the reference has no per-head q/k norm")
    return Arch(float(c["rms_norm_eps"]), float(c["rope_theta"]),
                int(c["num_experts_per_tok"]),
                int(c["num_attention_heads"]),
                int(c["num_key_value_heads"]), int(c["head_dim"]))


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(a, b, mode: str):
    """a @ b in float32; in fp8 mode both operands are rounded first."""
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(F32)


def rope(x, pos, theta):
    """x [S, heads, Dh]; rotate-half RoPE at positions pos [S]."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None, None] * freqs
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(x, lw, a: Arch, mode: str):
    s = x.shape[0]
    pos = jnp.arange(s)
    u = rmsnorm(x, lw["ln1"], a.eps)
    q = mm(u, lw["wq"], mode).reshape(s, a.heads, a.head_dim)
    k = mm(u, lw["wk"], mode).reshape(s, a.kv_heads, a.head_dim)
    v = mm(u, lw["wv"], mode).reshape(s, a.kv_heads, a.head_dim)
    q, k = rope(q, pos, a.theta), rope(k, pos, a.theta)
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


def moe(h, lw, a: Arch, mode: str):
    u = rmsnorm(h, lw["ln2"], a.eps)
    p = jax.nn.softmax(mm(u, lw["router"], mode), axis=-1)     # [S, E]
    top, idx = jax.lax.top_k(p, a.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)

    def one_expert(y, xs):
        wg, wu, wd, ge = xs
        f = jax.nn.silu(mm(u, wg, mode)) * mm(u, wu, mode)
        return y + ge[:, None] * mm(f, wd, mode), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return h + y


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def _layer(x, lw, a: Arch, mode: str):
    with jax.default_matmul_precision("highest"):
        return moe(attention(x, lw, a, mode), lw, a, mode)


_layer_jit = jax.jit(_layer, static_argnames=("a", "mode"))


def _head(x, final_norm, unembed, at, a: Arch, mode: str):
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(x[at], final_norm, a.eps)
        return mm(u, unembed.T, mode)


_head_jit = jax.jit(_head, static_argnames=("a", "mode"))

PAD = 512   # sequences are padded to a multiple of this (bounded compiles)


def logits(w: dict, c: dict, tokens: np.ndarray, at: np.ndarray,
           mode: str = "f32"):
    """Logits [len(at), V] of one sequence ``tokens`` at positions ``at``.
    Padding at the end cannot reach earlier positions (causal)."""
    a = arch(c)
    n = len(tokens)
    padded = np.zeros(-(-n // PAD) * PAD, np.int32)
    padded[:n] = tokens
    x = _embed(w["embed"], jnp.asarray(padded))
    layers = w["layers"]
    for i in range(c["num_hidden_layers"]):
        lw = {k: v[i] for k, v in layers.items()}
        x = _layer_jit(x, lw, a, mode)
    unembed = w.get("unembed", w["embed"])
    return _head_jit(x, w["final_norm"], unembed, jnp.asarray(at), a, mode)
