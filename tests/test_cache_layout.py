"""CacheLayout (generic per-request segment extract/restore) roundtrips for
every model family's cache structure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, reduced
from repro.models import get_model
from repro.serving.kvcache import CacheLayout, PagedCacheLayout
from repro.serving.workers import AttentionWorker, ClusterSlotView


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "gemma2_2b", "mixtral_8x7b",
                                  "zamba2_7b", "xlstm_350m",
                                  "whisper_small"])
def test_request_state_roundtrip(arch, key):
    cfg = reduced(arch)
    api = get_model(cfg, num_aw=1, num_ew=2)
    layout = CacheLayout(api.init_cache)
    params = api.init_params(key)
    rs = api.init_route_state()
    batch = make_batch(cfg, 1, 8)
    _, req_cache = api.prefill(params, {k: v for k, v in batch.items()},
                               rs, max_seq=16)
    state = layout.request_state(req_cache, 0)

    # write into slot 2 of a 4-slot cache and read back
    glob = api.init_cache(4, 16)
    glob = layout.write_request_state(glob, 2, state)
    back = layout.request_state(glob, 2)
    for a, b in zip(state, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "mixtral_8x7b"])
def test_token_segment_roundtrip_attention(arch, key):
    cfg = reduced(arch)
    api = get_model(cfg, num_aw=1, num_ew=2)
    layout = CacheLayout(api.init_cache)
    params = api.init_params(key)
    rs = api.init_route_state()
    batch = make_batch(cfg, 2, 8)
    _, cache = api.prefill(params, batch, rs, max_seq=16)
    # segment-by-segment copy of slot 0 into a fresh cache slot 1
    fresh = api.init_cache(2, 16)
    segs = [layout.token_segment(cache, 0, t) for t in range(8)]
    fresh = layout.write_token_segments(fresh, 1, range(8), segs)
    want = layout.request_state(cache, 0)
    got = layout.request_state(fresh, 1)
    for a, b, kind in zip(want, got, layout.leaf_kind):
        if kind.startswith("attn_"):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def token_loop(layout, cache, slot, tokens, segs):
    """The restore write ``write_token_segments`` replaced, kept as its
    reference: one eager scatter per token and cache leaf."""
    paged = isinstance(layout, PagedCacheLayout)
    if paged:
        bt, leaves, treedef = layout._rest(cache)
    else:
        leaves, treedef = layout._leaves(cache)
    for t, seg in zip(tokens, segs):
        out = []
        for leaf, ax, kind, s in zip(leaves, layout.batch_axis,
                                     layout.leaf_kind, seg):
            if paged:
                page = bt[slot, (t % layout.max_seq) // layout.page_tokens]
                safe = jnp.where(page > 0, page, leaf.shape[ax])
                idx = (safe, t % layout.page_tokens)
            elif kind.startswith("attn_"):
                idx = (slot, t % leaf.shape[ax + 1])
            else:
                idx = (slot,)
            out.append(leaf.at[(slice(None),) * ax + idx].set(
                jnp.asarray(s, leaf.dtype), mode="drop"))
        leaves = out
    if paged:
        return layout._rebuild(bt, leaves, treedef)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def random_array(rng, shape, dtype) -> np.ndarray:
    if jnp.issubdtype(dtype, jnp.integer):
        return rng.integers(-1, 1000, shape).astype(dtype)
    return rng.normal(size=shape).astype(dtype)


def random_like(rng, a):
    return jnp.asarray(random_array(rng, a.shape, a.dtype))


def seg_shape(leaf, ax: int, kind: str):
    """A token segment's shape: the leaf without its slot (or page) axis
    and, for attention leaves, its position axis."""
    drop = (ax, ax + 1) if kind.startswith("attn_") else (ax,)
    return tuple(d for i, d in enumerate(leaf.shape) if i not in drop)


GAP = list(range(100)) + list(range(140, 200))


def case(arch, paged, restores, unmapped=None, name=""):
    lens = "+".join(str(len(t)) for _, t in restores)
    return pytest.param(arch, paged, restores, unmapped, id=f"{arch}-"
                        f"{'paged' if paged else 'contiguous'}-{name or lens}")


@pytest.mark.parametrize("arch,paged,restores,unmapped", [
    *[case("mixtral_8x7b", paged, [(1, range(n))])
      for paged in (True, False) for n in (1, 127, 128, 129, 300)],
    case("mixtral_8x7b", True, [(1, GAP)], name="gap"),
    case("mixtral_8x7b", False, [(1, GAP)], name="gap"),
    case("mixtral_8x7b", True, [(1, range(300))], unmapped=1,
         name="unmapped"),
    case("mixtral_8x7b", True, [(1, range(450))], name="wrap"),
    case("gemma2_2b", False, [(1, range(300))], name="ring"),
    case("zamba2_7b", False, [(1, range(129))], name="state"),
    case("mixtral_8x7b", True, [(0, range(1)), (1, range(129)),
                                (2, range(300))]),
    case("mixtral_8x7b", False, [(0, range(1)), (1, range(129)),
                                 (2, range(300))]),
])
def test_write_token_segments_match_the_token_loop(arch, paged, restores,
                                                   unmapped):
    """The batched restore write equals the token-by-token loop bit for
    bit: block edges, gaps, an unmapped block (dropped, the null page
    untouched), a wrapping ring (the latest token wins), state leaves (the
    last snapshot wins); and its shapes never change, so restores of any
    length share one compiled program."""
    cfg = reduced(arch)
    api = get_model(cfg, num_aw=1, num_ew=2)
    rng = np.random.default_rng(0)
    max_seq = 384
    if paged:
        layout = PagedCacheLayout(api.init_cache, 128, max_seq)
        cache = layout.make_cache(api.init_cache, 3, num_pages=10)
        bt = np.arange(1, 10, dtype=np.int32).reshape(3, 3)
        if unmapped is not None:
            bt[1, unmapped] = 0
        cache = {k: v if k == "bt" else jax.tree_util.tree_map(
                     lambda a: random_like(rng, a), v)
                 for k, v in cache.items()}
        cache = layout.set_block_table(cache, bt)
        leaves = layout._rest(cache)[1]
    else:
        layout = CacheLayout(api.init_cache)
        cache = jax.tree_util.tree_map(lambda a: random_like(rng, a),
                                       api.init_cache(3, max_seq))
        leaves = layout._leaves(cache)[0]
    # XLA leaves the order of duplicate scatter indices undefined (the
    # CPU happens to apply them in order): no call may name a position
    # twice, nor a position another call of the same restore names
    calls = []
    write = layout._write_rows_fn

    def recorded(cache, slot, idx, vals):
        calls.append(idx)
        return write(cache, slot, idx, vals)

    layout._write_rows_fn = recorded
    start = cache
    want = got = cache
    for slot, tokens in restores:
        segs = [[random_array(rng, seg_shape(leaf, ax, kind), leaf.dtype)
                 for leaf, ax, kind in zip(leaves, layout.batch_axis,
                                           layout.leaf_kind)]
                for _ in tokens]
        want = token_loop(layout, want, slot, list(tokens), segs)
        calls.clear()
        got = layout.write_token_segments(got, slot, list(tokens), segs)
        assert len(calls) == -(-len(tokens) // layout.block_tokens)
        if paged:
            named = [(np.concatenate(calls), max_seq)]
        else:
            named = [(np.concatenate([c[i] for c in calls]),
                      leaf.shape[ax + 1])
                     for i, (leaf, ax, kind) in enumerate(zip(
                         leaves, layout.batch_axis, layout.leaf_kind))
                     if kind.startswith("attn_")]
        for ix, bound in named:
            ix = ix[(ix >= 0) & (ix < bound)]
            assert len(np.unique(ix)) == len(ix)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if paged:
        for a, b, ax in zip(layout._rest(start)[1], layout._rest(got)[1],
                            layout.batch_axis):
            np.testing.assert_array_equal(np.take(np.asarray(a), 0, ax),
                                          np.take(np.asarray(b), 0, ax))
    assert write._cache_size() == 1


def test_attention_nodes_detected():
    cfg = reduced("whisper_small")
    api = get_model(cfg)
    layout = CacheLayout(api.init_cache)
    kinds = set(layout.leaf_kind)
    assert "attn_k" in kinds and "attn_pos" in kinds
    # cross-KV has no pos -> classified as state
    assert "state" in kinds


def test_segment_nbytes_matches_appendix_c():
    """Attention token segments have size C = 2*Hkv*head_dim*bytes per
    layer (paper App. C)."""
    cfg = reduced("qwen2_1_5b")
    api = get_model(cfg)
    layout = CacheLayout(api.init_cache)
    cache = api.init_cache(1, 8)
    seg = layout.token_segment(cache, 0, 0)
    attn_bytes = layout.segment_nbytes(seg, attn_only=True)
    # pos leaves add 4 bytes per layer-stack entry; subtract them
    pos_bytes = sum(np.asarray(s).nbytes
                    for s, k in zip(seg, layout.leaf_kind)
                    if k == "attn_pos")
    per_layer = 2 * cfg.num_kv_heads * cfg.head_dim_ * 4  # f32 here
    assert attn_bytes - pos_bytes == cfg.num_layers * per_layer


def test_slot_partitions_and_failure():
    from repro.core.checkpoint import CheckpointStore
    import jax.numpy as _jnp
    from repro.core.refe import RouteState
    store = CheckpointStore()
    aws = [AttentionWorker(a, a * 4, (a + 1) * 4, store) for a in range(2)]
    sm = ClusterSlotView(aws, 8)
    s0 = sm.alloc(0)
    s1 = sm.alloc(1)
    assert sm.aw_of(s0) == 0 and sm.aw_of(s1) == 1
    rs = RouteState(candidates=_jnp.zeros((0, 2), _jnp.int32),
                    ew_health=_jnp.ones((2,), bool),
                    aw_health=_jnp.ones((2,), bool),
                    slot_expert=_jnp.zeros((0,), _jnp.int32),
                    slot_owner=_jnp.zeros((0,), _jnp.int32),
                    split_slot=_jnp.zeros((0,), _jnp.int32))
    rs = aws[0].fail(rs)
    assert not bool(rs.aw_health[0])
    assert sm.free_count(0) == 0
    assert sm.free_count(1) == 3
    rs = aws[0].provision(rs, in_use=set())
    assert bool(rs.aw_health[0])
    assert sm.free_count(0) == 4
