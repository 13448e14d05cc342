"""The Qwen3 family (``bench/reference/qwen3.py``) against the served path
on the CPU, at small widths with Qwen3's ratio of experts to experts per
token (32 experts, top-2: E/k = 16, as 128 / 8).

Served through ``session.run`` (prefill chunks, then paged decode) the
small cell is correct with no route dropped; at Mixtral's capacity factor
of 4.0 it drops routes and is not correct, and a program that cannot serve
dropless is refused at set-up; checked by a reference that skips the q/k
norm it is not correct. The weights and reference logits of
every configuration are deterministic per seed, and the q/k norm sits
where Qwen3 puts it: per head, over head_dim, with eps 1e-6, before RoPE."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_tiny import SMALL_LIMIT, run_small, small_cell

from harness import spec, weights

CELL = "qwen3-30b-a3b-4l.chat"
CONFIGS = {w["config"]: w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]}


def dropless_cell():
    """The Qwen3 cell at small widths, 32 experts top-2, in float32."""
    cell = small_cell(CELL, experts=32)
    c = cell.config
    c[spec.family(c).KEYS["K"]] = 2
    c["torch_dtype"] = "float32"
    return cell


@pytest.fixture(scope="module")
def served():
    return run_small(dropless_cell(), 4, seconds=2.0, traced=True)


def test_the_small_cell_keeps_qwen3s_ratio():
    d = weights.dims(dropless_cell().config)
    assert d["E"] // d["K"] == 16 and (d["H"], d["Hkv"]) == (4, 2)


def test_served_dropless_and_correct(served):
    assert served["correct"] is True
    assert served["compared"]["dropped_expert_routes"]["value"] == 0
    assert served["compared"]["clipped_mean_gap"]["value"] <= SMALL_LIMIT


def test_a_traced_run_reads_the_expert_row_fill(served):
    """The share of computed expert rows that carry a token, from the
    program's device spans: above 0, at most 100% (on the CPU no device
    metric comes back)."""
    fill = served["metrics"]["expert_row_fill.qwen3-chat"]["value"]
    assert 0 < fill <= 100
    assert {"decode_step_ms.qwen3-chat", "prefill_chunk_ms.qwen3-chat",
            "step_mfu.qwen3-chat"} <= set(served["metrics"])
    assert "device_idle.qwen3-chat" not in served["metrics"]


def test_at_capacity_factor_4_routes_drop(monkeypatch):
    from repro.launch import serve
    monkeypatch.setattr(serve, "dropless_capacity_factor", lambda moe: 4.0)
    out = run_small(dropless_cell(), 4, seconds=2.0)
    assert out["correct"] is False
    assert out["compared"]["dropped_expert_routes"]["value"] > 0


def test_a_program_that_drops_routes_is_refused(monkeypatch):
    """A program whose ``build_server`` fixes the factor at 4.0 (it has no
    ``dropless_capacity_factor``) cannot serve this family as published:
    set-up refuses it before any weight is drawn."""
    from repro.launch import serve
    monkeypatch.delattr(serve, "dropless_capacity_factor")
    c = dropless_cell().config
    with pytest.raises(ValueError, match="served dropless"):
        spec.family(c).model_config(c, "qwen3-small")


def test_a_reference_without_the_qk_norm_is_not_correct(monkeypatch):
    cell = dropless_cell()
    fam = spec.family(cell.config)
    arch = fam.arch
    monkeypatch.setattr(fam, "arch",
                        lambda c: arch(c)._replace(qk_norm=False))
    out = run_small(cell, 4, seconds=2.0)
    assert out["correct"] is False
    assert out["compared"]["dropped_expert_routes"]["value"] == 0
    assert out["compared"]["clipped_mean_gap"]["value"] > SMALL_LIMIT


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_weights_and_reference_logits_are_the_seeds(config):
    c = small_cell(CONFIGS[config]).config
    fam = spec.family(c)
    tokens = np.random.default_rng(3).integers(0, 256, 24, dtype=np.int32)
    at = np.arange(4, 23)
    runs = [weights.make_weights(c, s) for s in (5, 5, 6)]
    leaves = [jax.tree_util.tree_leaves(w) for w in runs]
    assert all(np.array_equal(bits(a), bits(b))
               for a, b in zip(leaves[0], leaves[1]))
    assert not all(np.array_equal(bits(a), bits(b))
                   for a, b in zip(leaves[0], leaves[2]))
    first, again = (np.asarray(fam.logits(w, c, tokens, at))
                    for w in runs[:2])
    assert np.array_equal(first, again)


def head_norm(x, heads, scale, eps):
    """Each of ``heads`` heads RMS-normed over its own head_dim."""
    x = x.reshape(heads, -1)
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half RoPE of [heads, Dh] at position ``pos``."""
    half = x.shape[-1] // 2
    ang = pos * theta ** (-np.arange(0, 2 * half, 2) / (2 * half))
    x1, x2 = x[:, :half], x[:, half:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


@pytest.mark.parametrize("scale", [1.0, 1e-3])   # 1e-3: eps is felt
def test_qk_norm_per_head_before_rope(scale):
    """One token at position 7, by hand in float64, against the reference
    and the program's own projections."""
    c = dropless_cell().config
    fam = spec.family(c)
    a = fam.arch(c)
    assert a.eps == c["rms_norm_eps"] == 1e-6
    w = weights.make_weights(c, 9)
    lw = {k: np.asarray(v[0], np.float64) for k, v in w["layers"].items()}
    pos = 7
    u = scale * np.random.default_rng(9).normal(size=(1, c["hidden_size"]))
    q, k = u[0] @ lw["wq"], u[0] @ lw["wk"]
    want_q = rope(head_norm(q, a.heads, lw["q_norm"], a.eps), pos, a.theta)
    want_k = rope(head_norm(k, a.kv_heads, lw["k_norm"], a.eps), pos,
                  a.theta)
    close = dict(rtol=2e-5, atol=2e-5)

    got_q, got_k, _ = fam.project(
        jnp.asarray(u, jnp.float32),
        {n: jnp.asarray(v, jnp.float32) for n, v in lw.items()}, a,
        jnp.asarray([pos]), "f32")
    np.testing.assert_allclose(np.asarray(got_q[0]), want_q, **close)
    np.testing.assert_allclose(np.asarray(got_k[0]), want_k, **close)

    from repro.models import attention
    from repro.models.layers import apply_rope
    cfg = fam.model_config(c, "qwen3-small")
    attn = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x[0], jnp.float32),
        fam.program_params(w, c)["blocks"][0]["attn"])
    x = jnp.asarray(u, jnp.float32)[None]              # [B 1, S 1, D]
    positions = jnp.asarray([[pos]])
    with jax.default_matmul_precision("highest"):
        prog_q = apply_rope(attention._project_q(cfg, attn, x), positions,
                            cfg.rope_theta)
        prog_k = apply_rope(attention._project_kv(cfg, attn, x)[0],
                            positions, cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(prog_q[0, 0]), want_q, **close)
    np.testing.assert_allclose(np.asarray(prog_k[0, 0]), want_k, **close)

    # the placements this is not: one norm over all heads, or after RoPE
    all_heads = rope(head_norm(q, 1, 1.0, a.eps).reshape(a.heads, -1) *
                     lw["q_norm"], pos, a.theta)
    after_rope = head_norm(rope(q.reshape(a.heads, -1), pos, a.theta),
                           a.heads, lw["q_norm"], a.eps)
    for other in (all_heads, after_rope):
        assert not np.allclose(other, want_q, **close)


def test_the_cell_is_the_published_model_cut_in_depth():
    c = spec.load_cell(CELL).config
    d = weights.dims(c)
    assert (d["D"], d["H"], d["Hkv"], d["Dh"], d["E"], d["K"], d["F"],
            d["V"]) == (2048, 32, 4, 128, 128, 8, 768, 151936)
    assert d["L"] == 4 and c["qk_norm"] is True
    assert spec.family(c).weight_shapes(c)["layers"]["q_norm"] == (4, 128)
