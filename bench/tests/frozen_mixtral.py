"""The Mixtral weight layout and float32 reference as they stood when each
configuration came to bring its own family module, frozen: the tests
compare ``bench/reference/mixtral.py`` and ``harness.weights`` with them
bit for bit, so that no reading of a Mixtral cell moves unseen.

Kept as it was, equations and all; do not edit to follow the live code.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

def dims(c: dict) -> dict:
    """The widths of configuration ``c`` under short names."""
    return {
        "L": c["num_hidden_layers"], "D": c["hidden_size"],
        "H": c["num_attention_heads"], "Hkv": c["num_key_value_heads"],
        "Dh": c["head_dim"], "V": c["vocab_size"],
        "E": c["num_local_experts"], "K": c["num_experts_per_tok"],
        "F": c["intermediate_size"],
    }


def weight_shapes(c: dict) -> dict:
    d = dims(c)
    L, D, H, Hkv, Dh, V, E, F = (d[k] for k in
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


def make_weights(c: dict, seed: int):
    """Every weight of configuration ``c`` from ``seed``, on the default
    device, in the dtype it is served in, in one jitted call. Matrices are
    normal with std 1/sqrt(fan-in) (the embedding std 1); norm scales are
    1 + N(0, 0.1), so that a path that skipped a norm's scale would show."""
    dtype = jnp.dtype(c["torch_dtype"])
    shapes = weight_shapes(c)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, shape, path in zip(keys, flat, paths):
            if "norm" in path or "ln" in path:
                leaf = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif "unembed" in path:        # applied as h @ unembed.T
                leaf = jax.random.normal(k, shape, jnp.float32) * \
                    (shape[-1] ** -0.5)
            elif "embed" in path:
                leaf = jax.random.normal(k, shape, jnp.float32)
            else:                          # [..., in, out]
                leaf = jax.random.normal(k, shape, jnp.float32) * \
                    (shape[-2] ** -0.5)
            leaves.append(leaf.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    # the seed enters as two 32-bit words: seeds wider than 32 bits differ
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.jit(draw)(np.uint32(lo), np.uint32(hi))


F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


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
