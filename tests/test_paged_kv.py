"""Paged KV cache (serving/kvcache.py PagedCacheLayout/PagePool + the
block-table decode kernel + the paged prefix plane).

Acceptance bar (ISSUE 8):
  * a paged engine is bit-identical to the contiguous engine — warm
    prefix turns, preemption-free decode, and decode under AW failure all
    emit the same tokens;
  * random interleaved adopt/extend/evict/fail sequences never double-free
    or leak a physical page (seeded-random property test over the
    PagePool oracle, at both the allocator and the engine level);
  * placement changes, prefix hits, and failover add ZERO new jit traces
    on the paged engine;
  * the block-table Pallas decode kernel (interpret mode) is bitwise
    identical to the fused contiguous kernel at block_k = page_tokens,
    and the ops-level fallback matches the reference oracle;
  * the cluster-wide radix index routes new sessions to the AW holding
    their prefix, and migration carries a hot prefix to a free AW through
    the checkpoint-replay path.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.kernels import ops, ref as kref
from repro.kernels.decode_attention import (decode_attention_fused,
                                            decode_attention_paged)
from repro.serving.api import RequestSpec
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.kvcache import PagePool


def make_engine(**kw):
    cfg = reduced("mixtral_8x7b", cap_factor=4.0)
    defaults = dict(max_batch=4, max_seq=64, num_aw=2, num_ew=2,
                    chunk_token_budget=8, placement="session_affinity",
                    prefix_cache_slots=2, checkpoint=True)
    defaults.update(kw)
    return InferenceEngine(cfg, EngineConfig(**defaults),
                           jax.random.PRNGKey(0))


def drain(eng, hs, max_steps=400):
    n = 0
    while not all(h.done() for h in hs) and n < max_steps:
        eng.step()
        for rid in [r.rid for r in eng.requests.values() if r.done]:
            eng.release_request(rid)
        n += 1
    assert all(h.done() for h in hs), "run did not finish"
    for rid in [r.rid for r in eng.requests.values() if r.done]:
        eng.release_request(rid)


def submit_run(eng, rid, prompt, max_new=4, session=None):
    h = eng.client.submit(RequestSpec(rid=rid, prompt=prompt,
                                      max_new=max_new, session=session))
    drain(eng, [h])
    return list(h.tokens())


def prompts_chain(seed=11, lens=(24, 8, 6), vocab=200):
    """Multi-turn chat shape: each prompt extends the previous one."""
    rng = np.random.default_rng(seed)
    out, cur = [], np.zeros((0,), np.int32)
    for n in lens:
        cur = np.concatenate(
            [cur, rng.integers(1, vocab, size=(n,)).astype(np.int32)])
        out.append(cur)
    return out


# --------------------------------------------------------------------------
# allocator property test: seeded-random interleavings, oracle-checked
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 1234, 777777])
def test_pagepool_fuzz_never_leaks_or_double_frees(seed):
    """Random interleaved extend/adopt/snapshot/evict/fail sequences keep
    every allocator invariant (each page free exactly once XOR allocated,
    bt only references live pages), and a full drain returns the pool to
    empty — no leak, no double free. (hypothesis is not available in this
    environment; seeded random.Random plays the same role.)"""
    rng = random.Random(seed)
    num_slots, nblk = 4, 4
    pool = PagePool(num_slots, 2, nblk, 8)
    entries = []                      # prefix entries: lists of pages
    for _ in range(3000):
        op = rng.randrange(5)
        slot = rng.randrange(num_slots)
        aw = pool.aw_of_slot(slot)
        if op == 0:                   # extend: map one more block
            blk = pool.mapped_blocks(slot)
            if blk < nblk and pool.free_pages(aw):
                pool.map_block(slot, blk, pool.alloc(aw))
        elif op == 1:                 # snapshot: entry pins a slot's pages
            pages = pool.slot_pages(slot)
            if pages:
                k = rng.randrange(1, len(pages) + 1)
                for p in pages[:k]:
                    pool.incref(p)
                entries.append(list(pages[:k]))
        elif op == 2:                 # adopt: empty slot maps entry pages
            if entries and pool.mapped_blocks(slot) == 0:
                e = rng.choice(entries)
                for i, p in enumerate(e[:nblk]):
                    pool.incref(p)
                    pool.map_block(slot, i, p)
        elif op == 3:                 # evict: tail-first partial trim
            if entries:
                e = rng.choice(entries)
                if e:
                    pool.decref(e.pop())
                if not e:
                    entries.remove(e)
        else:                         # release / fail: unmap whole slot
            pool.release_slot(slot)
        pool.check()
    for s in range(num_slots):        # drain everything
        pool.release_slot(s)
    for e in entries:
        for p in e:
            pool.decref(p)
    pool.check()
    st = pool.stats()
    assert st["pages_used"] == 0 and st["pages_shared"] == 0


def test_paged_engine_fuzz_never_leaks(monkeypatch=None):
    """Engine-level interleaving: submissions (adoption), decode steps
    (copy-on-extend), releases (offers/evictions), and AW fail/provision
    cycles keep the pool oracle green; after a full drain + cache purge
    every physical page is free."""
    rng = random.Random(99)
    eng = make_engine(kv_page_tokens=8)
    chain = prompts_chain(seed=5, lens=(16, 6, 6, 6))
    sessions = ["a", "b", "c"]
    hs, counter = [], iter(range(10000))
    for _ in range(90):
        op = rng.random()
        if op < 0.3 and len(eng.requests) < 3:
            s = rng.choice(sessions)
            p = chain[rng.randrange(len(chain))]
            hs.append(eng.client.submit(RequestSpec(
                rid=f"{s}-{next(counter)}", prompt=p,
                max_new=rng.randrange(2, 5), session=s)))
        elif op < 0.4:
            dead = [w.aw_id for w in eng.aws if not w.alive]
            live = [w.aw_id for w in eng.aws if w.alive]
            if dead:
                eng.provision_aw(dead[0])
            elif len(live) > 1:
                eng.fail_aw(rng.choice(live))
                eng.recover_aw_requests(now=float(eng.steps))
        else:
            eng.step()
            for rid in [r.rid for r in eng.requests.values() if r.done]:
                eng.release_request(rid)
        eng.pages.check()
    for w in eng.aws:
        if not w.alive:
            eng.provision_aw(w.aw_id)
    drain(eng, hs)
    eng.pages.check()
    # purge the caches: every remaining reference is a prefix entry's
    for w in eng.aws:
        for eid in list(w.prefix_cache.entries):
            eng._kv_free_pages(w.prefix_cache.remove_entry(eid))
    eng.pages.check()
    assert eng.pages.stats()["pages_used"] == 0


# --------------------------------------------------------------------------
# bit-identity vs the contiguous engine
# --------------------------------------------------------------------------

def _warm_turn_tokens(**kw):
    eng = make_engine(**kw)
    chain = prompts_chain()
    out = [submit_run(eng, f"sess-{i}", p, session="sess")
           for i, p in enumerate(chain)]
    return eng, out


def test_paged_matches_contiguous_warm_turns():
    """Multi-turn prefix hits: the paged engine adopts shared pages by
    reference (copy-on-extend at the boundary) and emits exactly the
    contiguous engine's tokens, with real page sharing observed."""
    ceng, want = _warm_turn_tokens()
    peng, got = _warm_turn_tokens(kv_page_tokens=16)
    assert got == want
    cs, ps = ceng.gateway.stats, peng.gateway.stats
    assert (ps.prefix_hits, ps.prefix_hit_tokens) == \
        (cs.prefix_hits, cs.prefix_hit_tokens)
    assert ps.prefix_hits > 0
    peng.pages.check()
    assert peng.pages.stats()["pages_shared"] > 0


@pytest.mark.parametrize("seg_len", [1, 4])
def test_paged_matches_contiguous_under_aw_failure(seg_len):
    """AW0 dies mid-run (mid-segment at decode_segment_len=4) with
    requests in flight; recovery replays committed checkpoints into fresh
    pages and every request finishes with the contiguous engine's exact
    tokens."""
    results = {}
    for mode, kw in [("contig", {}), ("paged", dict(kv_page_tokens=16))]:
        eng = make_engine(decode_segment_len=seg_len, **kw)
        hs = []
        for i in range(3):
            p = np.random.default_rng(100 + i).integers(
                1, 200, size=(12 + 3 * i,)).astype(np.int32)
            hs.append(eng.client.submit(RequestSpec(
                rid=f"s{i}-0", prompt=p, max_new=6, session=f"s{i}")))
        for _ in range(6):
            eng.step()
        eng.fail_aw(0)
        eng.recover_aw_requests(now=float(eng.steps))
        if eng.pages is not None:
            eng.pages.check()
        drain(eng, hs)
        if eng.pages is not None:
            eng.pages.check()
        results[mode] = [list(h.tokens()) for h in hs]
    assert results["paged"] == results["contig"]


def test_paged_whole_prompt_prefill_matches_contiguous():
    """Without the chunked plane, prompts take the whole-prompt (padded)
    prefill, whose per-request cache is contiguous: a paged engine
    scatters it into pages and emits the contiguous engine's tokens."""
    results = {}
    for mode, kw in [("contig", {}), ("paged", dict(kv_page_tokens=16))]:
        eng = make_engine(chunk_token_budget=0, prefix_cache_slots=0, **kw)
        hs = [eng.client.submit(RequestSpec(
            rid=f"w{i}", prompt=np.random.default_rng(200 + i).integers(
                1, 200, size=(9 + 11 * i,)).astype(np.int32), max_new=5))
            for i in range(3)]
        drain(eng, hs)
        if eng.pages is not None:
            eng.pages.check()
        results[mode] = [list(h.tokens()) for h in hs]
    assert results["paged"] == results["contig"]


def test_paged_zero_new_traces():
    """The whole paged lifecycle — cold admission, warm prefix hits,
    AW failover + restoration — re-uses the first-turn jit traces: block
    tables are data, not structure."""
    eng = make_engine(kv_page_tokens=16)
    chain = prompts_chain()
    submit_run(eng, "sess-0", chain[0], session="sess")
    base = eng._decode._cache_size() + eng.decode_plane.segment_traces()
    submit_run(eng, "sess-1", chain[1], session="sess")      # warm hit
    h = eng.client.submit(RequestSpec(rid="sess-2", prompt=chain[2],
                                      max_new=4, session="sess"))
    for _ in range(2):
        eng.step()
    victim = next(w.aw_id for w in eng.aws
                  if any(r._aw == w.aw_id for r in eng.requests.values()))
    eng.fail_aw(victim)
    eng.recover_aw_requests(now=float(eng.steps))
    drain(eng, [h])
    assert eng._decode._cache_size() + \
        eng.decode_plane.segment_traces() == base


# --------------------------------------------------------------------------
# block-table decode kernel
# --------------------------------------------------------------------------

def _paged_case(seed, b, hkv, h, dh, nblk, pt, npages):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pk = jax.random.normal(ks[0], (npages, pt, hkv, dh), jnp.float32)
    pv = jax.random.normal(ks[1], (npages, pt, hkv, dh), jnp.float32)
    q = jax.random.normal(ks[2], (b, h, dh), jnp.float32)
    k1 = jax.random.normal(ks[3], (b, hkv, dh), jnp.float32)
    v1 = jax.random.normal(ks[4], (b, hkv, dh), jnp.float32)
    rng = np.random.default_rng(seed)
    # rows share pages (the prefix-sharing layout) and may hold nulls
    bt = rng.integers(1, npages, size=(b, nblk)).astype(np.int32)
    bt[0, 0] = bt[1, 0] if b > 1 else bt[0, 0]     # a genuinely shared page
    pos = jnp.asarray(rng.integers(pt, nblk * pt, size=(b,)), jnp.int32)
    # physical pages carry their own positions; null page 0 is all -1
    ppos = np.full((npages, pt), -1, np.int32)
    for pid in range(1, npages):
        ppos[pid] = rng.integers(0, nblk * pt, size=(pt,))
    for i in range(b):                 # make each row's view causal-valid
        for j in range(nblk):
            ppos[bt[i, j]] = np.arange(j * pt, (j + 1) * pt)
    ppos[0] = -1
    return q, pk, pv, jnp.asarray(ppos), jnp.asarray(bt), k1, v1, pos


@pytest.mark.parametrize("b,hkv,h,dh", [(2, 2, 8, 64), (3, 1, 4, 32)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_kernel_matches_fused(b, hkv, h, dh, softcap):
    """Interpret-mode Pallas: the block-table kernel gathering pages
    through scalar prefetch is BITWISE identical to the fused contiguous
    kernel at block_k = page_tokens (same accumulation order)."""
    nblk, pt, npages = 4, 16, 9
    q, pk, pv, ppos, bt, k1, v1, pos = _paged_case(
        3, b, hkv, h, dh, nblk, pt, npages)
    got = decode_attention_paged(q, pk, pv, ppos, bt, k1, v1, pos,
                                 softcap=softcap, interpret=True)
    flat = np.asarray(bt).reshape(-1)
    ck = pk[flat].reshape(b, nblk * pt, hkv, dh)
    cv = pv[flat].reshape(b, nblk * pt, hkv, dh)
    cpos = ppos[flat].reshape(b, nblk * pt)
    want = decode_attention_fused(q, ck, cv, cpos, k1, v1, pos,
                                  window=0, softcap=softcap, block_k=pt,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_ops_fallback_matches_ref():
    """The non-Pallas dispatch (gather + reference partial/combine) agrees
    with the oracle on the gathered contiguous view."""
    b, hkv, h, dh, nblk, pt, npages = 2, 2, 8, 64, 4, 16, 9
    q, pk, pv, ppos, bt, k1, v1, pos = _paged_case(
        4, b, hkv, h, dh, nblk, pt, npages)
    got = ops.decode_attention_paged(q, pk, pv, ppos, bt, k1, v1, pos)
    flat = np.asarray(bt).reshape(-1)
    ck = pk[flat].reshape(b, nblk * pt, hkv, dh)
    cv = pv[flat].reshape(b, nblk * pt, hkv, dh)
    cpos = ppos[flat].reshape(b, nblk * pt)
    want = kref.decode_attention_ref(q, ck, cv, jnp.asarray(cpos), k1, v1,
                                     pos, window=0, softcap=0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# cluster-wide routing + migration
# --------------------------------------------------------------------------

def test_global_index_routes_new_session_to_cached_aw():
    """A brand-new session whose prompt extends another session's cached
    prefix routes to the AW that holds it (one global trie lookup), hits,
    and still emits the contiguous engine's tokens."""
    chain = prompts_chain()
    results = {}
    for mode, kw in [("contig", {}),
                     ("paged", dict(kv_page_tokens=16,
                                    prefix_global_index=True))]:
        eng = make_engine(**kw)
        t1 = submit_run(eng, "alpha-0", chain[0], session="alpha")
        t2 = submit_run(eng, "beta-0", chain[1], session="beta")
        results[mode] = (t1, t2)
        if eng.pages is not None:
            assert eng.gateway.stats.prefix_global_hits >= 1
            assert eng.gateway.stats.prefix_hits >= 1
            eng.pages.check()
    assert results["paged"] == results["contig"]


def test_prefix_migration_follows_demand():
    """When the home AW has no slot headroom, the matched prefix migrates
    to a free AW via checkpoint replay and the arrival routes there: the
    hit survives the move and the output is unchanged."""
    chain = prompts_chain()
    eng = make_engine(kv_page_tokens=16, prefix_global_index=True,
                      prefix_migrate=True)
    want = [submit_run(make_engine(), f"w{i}", p, session=f"w{i}")
            for i, p in enumerate(chain[:2])]
    t1 = submit_run(eng, "alpha-0", chain[0], session="alpha")
    assert t1 == want[0]
    home = eng.prefix_plane.global_index.match(chain[1])[1]
    # saturate the home AW's partition so the router must migrate
    held = [eng.aws[home].slots.alloc()
            for _ in range(eng.aws[home].slots.free_count())]
    t2 = submit_run(eng, "beta-0", chain[1], session="beta")
    for s in held:
        eng.aws[home].slots.release(s)
    assert t2 == want[1]
    st = eng.gateway.stats
    assert st.prefix_migrated == 1 and st.prefix_global_hits >= 1
    assert st.prefix_hits >= 1
    new_home = eng.prefix_plane.global_index.match(chain[1])[1]
    assert new_home != home
    eng.pages.check()


def test_paged_eviction_prices_exclusive_pages():
    """Satellite fix: under page pressure the victim is the LRU entry and
    shared pages are never freed — only the refcount drops; the page
    stays live for its other holders."""
    eng = make_engine(kv_page_tokens=8, max_batch=2, num_aw=1, max_seq=32)
    pool = eng.pages
    cache = eng.aws[0].prefix_cache
    chain = prompts_chain(seed=3, lens=(10, 6))
    submit_run(eng, "s-0", chain[0], session="s")
    submit_run(eng, "s-1", chain[1], session="s")
    assert len(cache.entries) >= 1
    shared = [p for e in cache.entries.values() for p in e.pages
              if pool.ref[p] > 1]
    before = {p: int(pool.ref[p]) for p in shared}
    # drain the free list, then ask the cache to relieve the pressure
    aw = 0
    held = []
    while pool.free_pages(aw):
        held.append(pool.alloc(aw))
    freed = cache.evict_pages()
    assert freed, "eviction could not free a page"
    for p in freed:
        assert pool.ref[p] == 0
        assert p not in before, "a shared page was freed"
    for p in held:
        pool.decref(p)
    pool.check()
