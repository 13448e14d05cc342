"""Per-request KV/state slot management over the model cache pytree.

The model families expose caches with different structures (stacked attention
KV, Mamba states, xLSTM cells, whisper cross-KV). ``CacheLayout`` discovers,
once per model, (i) the batch axis of every leaf and (ii) which subtrees are
attention caches ({"k","v","pos"} triples), and then provides generic
per-request operations:

  * ``token_segment``   — the incremental checkpoint unit (paper §6.1):
      attention leaves -> the single KV column the decode step just wrote
      (size C = 2*Hkv*head_dim, App. C); state leaves (SSM/xLSTM/cross-KV)
      -> the current constant-size snapshot.
  * ``write_token_segments`` — per-request restoration (§6.2): inject a
      request's committed segments into any healthy AW's cache slot, a
      block of rows per compiled scatter.
  * ``request_state`` / ``write_request_state`` — whole-slot copy (used for
      request migration and the pause-checkpoint-resume baseline).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


# rows a contiguous layout's restore scatter takes per call (a paged
# layout takes one page's worth)
RESTORE_BLOCK = 128


def _stack_rows(segs, i: int, dtype, rows: int) -> np.ndarray:
    """Leaf ``i`` of every segment stacked on the host into one array of
    ``rows`` rows (zero padded) in the cache leaf's dtype."""
    out = np.zeros((rows,) + np.shape(segs[0][i]), dtype)
    out[:len(segs)] = np.stack([s[i] for s in segs])
    return out


def _last_of(keys: np.ndarray) -> np.ndarray:
    """Mask of the rows whose key recurs at no later row: the writes a
    token-by-token loop would leave standing. XLA leaves the order of
    duplicate scatter indices undefined, so the others never reach it."""
    _, first = np.unique(keys[::-1], return_index=True)
    keep = np.zeros(len(keys), bool)
    keep[len(keys) - 1 - first] = True
    return keep


class CacheLayout:
    def __init__(self, init_cache_fn):
        c1 = jax.eval_shape(lambda: init_cache_fn(1, 16))
        c2 = jax.eval_shape(lambda: init_cache_fn(2, 16))
        l1, self.treedef = jax.tree_util.tree_flatten_with_path(c1)
        l2, _ = jax.tree_util.tree_flatten_with_path(c2)
        self.paths: List[str] = []
        self.batch_axis: List[int] = []
        for (p1, a1), (_, a2) in zip(l1, l2):
            diffs = [i for i, (s1, s2) in enumerate(zip(a1.shape, a2.shape))
                     if s1 != s2]
            assert len(diffs) == 1, f"ambiguous batch axis at {p1}: {a1.shape}"
            self.paths.append(_path_str(p1))
            self.batch_axis.append(diffs[0])
        # attention nodes: parent paths having exactly k/v/pos children
        parents: Dict[str, set] = {}
        for p in self.paths:
            if "/" in p:
                par, leaf = p.rsplit("/", 1)
                parents.setdefault(par, set()).add(leaf)
        self.attn_parents = {par for par, kids in parents.items()
                             if {"k", "v", "pos"} <= kids}
        self.leaf_kind: List[str] = []
        for p in self.paths:
            par, _, leaf = p.rpartition("/")
            if par in self.attn_parents and leaf in ("k", "v", "pos"):
                self.leaf_kind.append("attn_" + leaf)
            else:
                self.leaf_kind.append("state")
        self.block_tokens = RESTORE_BLOCK
        self._write_rows_fn = jax.jit(self._write_rows_impl)

    # ------------------------------------------------------------------
    def _leaves(self, cache):
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        assert len(leaves) == len(self.paths)
        return leaves, treedef

    @staticmethod
    def _take(a, axis, idx):
        return jax.lax.index_in_dim(a, idx, axis, keepdims=False)

    @staticmethod
    def _put(a, axis, idx, val):
        return jnp.asarray(a).at[
            (slice(None),) * axis + (idx,)].set(jnp.asarray(val, a.dtype))

    # ------------------------------------------------------------------
    def token_segment(self, cache, slot: int, token: int) -> List[Any]:
        """Incremental checkpoint segment for (request slot, token idx)."""
        leaves, _ = self._leaves(cache)
        seg = []
        for leaf, ax, kind in zip(leaves, self.batch_axis, self.leaf_kind):
            per_req = self._take(leaf, ax, slot)     # drop batch axis
            if kind.startswith("attn_"):
                sc = per_req.shape[ax]  # position axis follows batch axis
                per_req = self._take(per_req, ax, token % sc)
            seg.append(np.asarray(per_req))
        return seg

    def write_token_segments(self, cache, slot: int, tokens, segs):
        """Per-request restoration (§6.2): write the committed segments
        ``segs`` of ``tokens`` (ascending token indices, gaps allowed) into
        ``slot``. An attention leaf takes each token's column at ``token %
        Sc``, where on a ring the latest token of a position wins; a state
        leaf takes the last token's snapshot. The rows are stacked on the
        host and go to the device ``block_tokens`` at a time through one
        compiled scatter whose shapes never change."""
        if not len(tokens):
            return cache
        leaves, _ = self._leaves(cache)
        toks = np.asarray(tokens, np.int64)
        k = self.block_tokens
        rows = -(-len(toks) // k) * k
        idx, vals = [], []
        for i, (leaf, ax, kind) in enumerate(zip(leaves, self.batch_axis,
                                                 self.leaf_kind)):
            if kind.startswith("attn_"):
                sc = leaf.shape[ax + 1]
                pos = toks % sc
                ix = np.full(rows, sc, np.int32)     # sc: dropped
                ix[:len(toks)] = np.where(_last_of(pos), pos, sc)
                idx.append(ix)
                vals.append(_stack_rows(segs, i, leaf.dtype, rows))
            else:
                idx.append(None)
                vals.append(np.asarray(segs[-1][i], leaf.dtype))
        slot = np.int32(slot)
        for b in range(0, rows, k):
            cache = self._write_rows_fn(
                cache, slot, [None if ix is None else ix[b:b + k]
                              for ix in idx],
                [v if ix is None else v[b:b + k]
                 for ix, v in zip(idx, vals)])
        return cache

    def _write_rows_impl(self, cache, slot, idx, vals):
        """One block of a restore: attention leaf ``i`` takes its rows
        ``vals[i]`` at ring positions ``idx[i]`` of ``slot`` (out of range:
        dropped); a state leaf takes its snapshot again, which leaves the
        same result however many blocks there are."""
        leaves, treedef = self._leaves(cache)
        out = []
        for leaf, ax, ix, v in zip(leaves, self.batch_axis, idx, vals):
            if ix is None:
                out.append(leaf.at[(slice(None),) * ax + (slot,)].set(v))
            else:
                out.append(leaf.at[(slice(None),) * ax + (slot, ix)].set(
                    jnp.moveaxis(v, 0, ax), mode="drop"))
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------------
    def make_batched_extractor(self):
        """One jitted gather for all active (slot, token) pairs — the
        AW-side analogue of posting all RDMA writes in a single doorbell.
        Returns fn(cache, slots [n], tokens [n]) -> list of leaves with a
        leading n axis."""
        batch_axes = list(self.batch_axis)
        kinds = list(self.leaf_kind)

        def extract(cache, slots, tokens):
            leaves, _ = jax.tree_util.tree_flatten(cache)
            out = []
            for leaf, ax, kind in zip(leaves, batch_axes, kinds):
                def one(slot, tok, leaf=leaf, ax=ax, kind=kind):
                    per = jax.lax.dynamic_index_in_dim(leaf, slot, ax,
                                                       keepdims=False)
                    if kind.startswith("attn_"):
                        sc = per.shape[ax]
                        per = jax.lax.dynamic_index_in_dim(
                            per, tok % sc, ax, keepdims=False)
                    return per

                out.append(jax.vmap(one)(slots, tokens))
            return out

        return jax.jit(extract)

    # ------------------------------------------------------------------
    def make_slot_range_extractor(self):
        """Bulk-segment gather for chunked prefill: one jitted call pulls
        the ``count`` contiguous token segments a chunk just wrote for one
        slot. Returns fn(cache, slot, start, count=<static>) -> list of
        leaves with a leading count axis (attention leaves: the KV columns
        at token indices [start, start+count); state leaves: the current
        snapshot repeated). ``count`` is static, so jit keys track the
        O(log) chunk-shape set, not every chunk length ever seen."""
        batch_axes = list(self.batch_axis)
        kinds = list(self.leaf_kind)

        def extract(cache, slot, start, *, count: int):
            leaves, _ = jax.tree_util.tree_flatten(cache)
            out = []
            for leaf, ax, kind in zip(leaves, batch_axes, kinds):
                per = jax.lax.dynamic_index_in_dim(leaf, slot, ax,
                                                   keepdims=False)
                if kind.startswith("attn_"):
                    sc = per.shape[ax]
                    sl = jax.lax.dynamic_slice_in_dim(
                        per, start % sc, count, axis=ax)
                    out.append(jnp.moveaxis(sl, ax, 0))
                else:
                    out.append(jnp.broadcast_to(
                        per[None], (count,) + per.shape))
            return out

        return jax.jit(extract, static_argnames=("count",))

    # ------------------------------------------------------------------
    def make_multi_slot_range_extractor(self):
        """Segment-drain gather: one jitted call pulls ``count`` contiguous
        token segments for MANY slots at once — the decode plane's
        per-segment checkpoint drain (every active request commits its
        segment's KV in a single device gather instead of one call each).
        Returns fn(cache, slots [n], starts [n], count=<static>) -> list
        of leaves with leading [n, count] axes. ``count`` static and rows
        pow2-padded upstream keep jit keys O(log seg_len · log max_batch)."""
        batch_axes = list(self.batch_axis)
        kinds = list(self.leaf_kind)

        def extract(cache, slots, starts, *, count: int):
            leaves, _ = jax.tree_util.tree_flatten(cache)
            out = []
            for leaf, ax, kind in zip(leaves, batch_axes, kinds):
                def one(slot, start, leaf=leaf, ax=ax, kind=kind):
                    per = jax.lax.dynamic_index_in_dim(leaf, slot, ax,
                                                       keepdims=False)
                    if kind.startswith("attn_"):
                        sc = per.shape[ax]
                        sl = jax.lax.dynamic_slice_in_dim(
                            per, start % sc, count, axis=ax)
                        return jnp.moveaxis(sl, ax, 0)
                    return jnp.broadcast_to(per[None],
                                            (count,) + per.shape)

                out.append(jax.vmap(one)(slots, starts))
            return out

        return jax.jit(extract, static_argnames=("count",))

    # ------------------------------------------------------------------
    def request_state(self, cache, slot: int) -> List[Any]:
        leaves, _ = self._leaves(cache)
        return [np.asarray(self._take(l, ax, slot))
                for l, ax in zip(leaves, self.batch_axis)]

    def write_request_state(self, cache, slot: int, state: List[Any]):
        leaves, treedef = self._leaves(cache)
        out = [self._put(l, ax, slot, s)
               for l, ax, s in zip(leaves, self.batch_axis, state)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def scrub_request_state(self, state: List[Any], valid_len: int
                            ) -> List[Any]:
        """Invalidate pad entries of a batched-prefill request state: any
        attention-cache entry holding a position >= ``valid_len`` gets
        ``pos`` = -1, which the decode kernels mask out. K/V payloads can
        stay — they are unreachable once the position is invalid. Only
        meaningful for pure attention caches (state leaves are recurrent
        summaries that padding must not reach in the first place)."""
        out = []
        for s, kind in zip(state, self.leaf_kind):
            if kind == "attn_pos":
                s = np.where(np.asarray(s) >= valid_len, -1, s)
            out.append(s)
        return out

    def scrub_slot(self, cache, slot: int, valid_len: int):
        """Invalidate positions >= ``valid_len`` of one slot in place:
        attention ``pos`` entries past the valid prefix become -1 (masked
        by the decode kernels); K/V payloads stay — unreachable once the
        position is invalid. This is prefix-cache adoption's counterpart
        of ``clear_slot``: the adopted prefix [0, valid_len) survives, the
        donor's stale tail does not. Only meaningful for pure attention
        caches (slot index == absolute position)."""
        leaves, treedef = self._leaves(cache)
        out = []
        for leaf, ax, kind in zip(leaves, self.batch_axis, self.leaf_kind):
            if kind == "attn_pos":
                per = self._take(leaf, ax, slot)
                per = jnp.where(per >= valid_len, -1, per)
                leaf = self._put(leaf, ax, slot, per)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    def clear_slot(self, cache, slot: int):
        """Reset one slot (releases a finished/failed request)."""
        leaves, treedef = self._leaves(cache)
        out = []
        for leaf, ax, kind in zip(leaves, self.batch_axis, self.leaf_kind):
            per = self._take(leaf, ax, slot)
            fill = jnp.full_like(per, -1) if kind == "attn_pos" \
                else jnp.zeros_like(per)
            out.append(self._put(leaf, ax, slot, fill))
        return jax.tree_util.tree_unflatten(treedef, out)

    def segment_nbytes(self, seg: List[Any], attn_only: bool = False) -> int:
        total = 0
        for s, kind in zip(seg, self.leaf_kind):
            if attn_only and not kind.startswith("attn_"):
                continue
            total += np.asarray(s).nbytes
        return total

    def prefill_paddable(self, cache, max_seq: int) -> bool:
        """True when slot index == absolute position for every leaf (pure
        attention cache, no ring wrap): the precondition for chunked
        prefill and prefix adoption."""
        leaves, _ = self._leaves(cache)
        if not all(k.startswith("attn_") for k in self.leaf_kind):
            return False
        return all(leaf.shape[ax + 1] >= max_seq
                   for leaf, ax, kind in zip(leaves, self.batch_axis,
                                             self.leaf_kind)
                   if kind == "attn_k")


# --------------------------------------------------------------------------
# paged layout: block tables over refcounted physical page pools
# --------------------------------------------------------------------------

class PagedCacheLayout:
    """CacheLayout twin for a PAGED cache (vLLM-style block tables).

    The paged cache pytree is the contiguous pytree with every leaf's
    per-slot rows replaced by a pool of physical pages — batch axis B ->
    page axis P, position axis Sc -> page extent ``page_tokens`` — plus
    one top-level block table ``bt`` [B, nblk] int32 shared by all layers
    (nblk * page_tokens == max_seq, so a slot's gathered pages reproduce
    its contiguous layout element-for-element). Page 0 is reserved: never
    allocated, positions -1 forever; unmapped block-table entries point at
    it so every gather reads a valid page and unmapped regions mask out
    exactly like an empty contiguous cache.

    Every read-side operation gathers the slot's pages into the contiguous
    per-slot view and then applies the contiguous logic, so checkpoint
    segments and request states are LAYOUT-INDEPENDENT: a segment written
    by a paged AW restores onto a contiguous engine and vice versa — the
    property prefix migration and failover restoration ride on.

    Paged mode is attention-only and full-attention-only (no SSM state
    leaves, no sliding-window ring buffers); the engine asserts both.
    """

    def __init__(self, init_cache_fn, page_tokens: int, max_seq: int):
        assert page_tokens > 0 and max_seq % page_tokens == 0, \
            (page_tokens, max_seq)
        self.inner = CacheLayout(init_cache_fn)
        assert all(k.startswith("attn_") for k in self.inner.leaf_kind), \
            "paged KV requires a pure attention cache"
        self.page_tokens = page_tokens
        self.max_seq = max_seq
        self.nblk = max_seq // page_tokens
        # mirrored for callers that introspect the layout generically
        self.paths = self.inner.paths
        self.batch_axis = self.inner.batch_axis
        self.leaf_kind = self.inner.leaf_kind
        self.attn_parents = self.inner.attn_parents
        self.block_tokens = page_tokens
        self._copy_page_fn = jax.jit(self._copy_page_impl)
        self._scrub_pages_fn = jax.jit(self._scrub_pages_impl)
        self._write_rows_fn = jax.jit(self._write_rows_impl)

    # ------------------------------------------------------------------
    def make_cache(self, init_cache_fn, batch: int, num_pages: int):
        """Build the paged cache: per-layer page pools (the contiguous
        init with batch=num_pages, max_seq=page_tokens) + the block
        table, all entries at the null page."""
        pools = init_cache_fn(num_pages, self.page_tokens)
        cache = dict(pools)
        cache["bt"] = jnp.zeros((batch, self.nblk), jnp.int32)
        return cache

    def _rest(self, cache):
        rest = {k: v for k, v in cache.items() if k != "bt"}
        leaves, treedef = jax.tree_util.tree_flatten(rest)
        assert len(leaves) == len(self.inner.paths)
        return cache["bt"], leaves, treedef

    def _rebuild(self, bt, leaves, treedef):
        rest = jax.tree_util.tree_unflatten(treedef, leaves)
        out = dict(rest)
        out["bt"] = bt
        return out

    def set_block_table(self, cache, bt_host):
        """Install the host block-table mirror on device (a tiny [B, nblk]
        int32 upload — the only per-allocation device traffic)."""
        out = dict(cache)
        out["bt"] = jnp.asarray(np.asarray(bt_host, np.int32))
        return out

    def _gather_slot(self, leaf, ax, row):
        """Contiguous per-slot view of one pool leaf through a block-table
        row [nblk]: [..., P, pt, ...] -> [..., nblk*pt, ...] at axis ax."""
        g = jnp.take(leaf, row, axis=ax)
        shp = leaf.shape[:ax] + (row.shape[0] * leaf.shape[ax + 1],) + \
            leaf.shape[ax + 2:]
        return g.reshape(shp)

    # ------------------------------------------------------------------
    def token_segment(self, cache, slot: int, token: int) -> List[Any]:
        bt, leaves, _ = self._rest(cache)
        pt = self.page_tokens
        page = bt[slot, (token % self.max_seq) // pt]
        off = token % pt
        seg = []
        for leaf, ax in zip(leaves, self.inner.batch_axis):
            per = jax.lax.index_in_dim(
                jax.lax.dynamic_index_in_dim(leaf, page, ax,
                                             keepdims=False),
                off, ax, keepdims=False)
            seg.append(np.asarray(per))
        return seg

    def write_token_segments(self, cache, slot: int, tokens, segs):
        """Per-request restoration into the slot's mapped pages, a page's
        worth of rows per call of one compiled scatter that reads the
        block-table row on the device. A token in an unmapped block (the
        null page: the host failed to pre-allocate) drops its write
        instead of corrupting the shared null page; of tokens that share
        a position modulo ``max_seq`` the latest wins."""
        if not len(tokens):
            return cache
        _, leaves, _ = self._rest(cache)
        toks = np.asarray(tokens, np.int64) % self.max_seq
        k = self.block_tokens
        rows = -(-len(toks) // k) * k
        tk = np.full(rows, -1, np.int32)             # -1: dropped
        tk[:len(toks)] = np.where(_last_of(toks), toks, -1)
        vals = [_stack_rows(segs, i, leaf.dtype, rows)
                for i, leaf in enumerate(leaves)]
        slot = np.int32(slot)
        for b in range(0, rows, k):
            cache = self._write_rows_fn(cache, slot, tk[b:b + k],
                                        [v[b:b + k] for v in vals])
        return cache

    def _write_rows_impl(self, cache, slot, tk, vals):
        bt, leaves, treedef = self._rest(cache)
        pt = self.page_tokens
        row = jax.lax.dynamic_index_in_dim(bt, slot, 0, keepdims=False)
        page = jnp.take(row, tk // pt, mode="clip")
        off = tk % pt
        out = []
        for leaf, ax, v in zip(leaves, self.inner.batch_axis, vals):
            dest = jnp.where((tk >= 0) & (page > 0), page, leaf.shape[ax])
            out.append(leaf.at[(slice(None),) * ax + (dest, off)].set(
                jnp.moveaxis(v, 0, ax), mode="drop"))
        return self._rebuild(bt, out, treedef)

    # ------------------------------------------------------------------
    def make_batched_extractor(self):
        batch_axes = list(self.inner.batch_axis)
        pt, max_seq = self.page_tokens, self.max_seq

        def extract(cache, slots, tokens):
            bt, leaves, _ = self._rest(cache)
            out = []
            for leaf, ax in zip(leaves, batch_axes):
                def one(slot, tok, leaf=leaf, ax=ax):
                    row = jax.lax.dynamic_index_in_dim(bt, slot, 0,
                                                       keepdims=False)
                    page = jax.lax.dynamic_index_in_dim(
                        row, (tok % max_seq) // pt, 0, keepdims=False)
                    per = jax.lax.dynamic_index_in_dim(leaf, page, ax,
                                                       keepdims=False)
                    return jax.lax.dynamic_index_in_dim(
                        per, tok % pt, ax, keepdims=False)

                out.append(jax.vmap(one)(slots, tokens))
            return out

        return jax.jit(extract)

    def make_slot_range_extractor(self):
        batch_axes = list(self.inner.batch_axis)
        max_seq = self.max_seq

        def extract(cache, slot, start, *, count: int):
            bt, leaves, _ = self._rest(cache)
            row = jax.lax.dynamic_index_in_dim(bt, slot, 0, keepdims=False)
            out = []
            for leaf, ax in zip(leaves, batch_axes):
                per = self._gather_slot(leaf, ax, row)
                sl = jax.lax.dynamic_slice_in_dim(
                    per, start % max_seq, count, axis=ax)
                out.append(jnp.moveaxis(sl, ax, 0))
            return out

        return jax.jit(extract, static_argnames=("count",))

    def make_multi_slot_range_extractor(self):
        batch_axes = list(self.inner.batch_axis)
        max_seq = self.max_seq

        def extract(cache, slots, starts, *, count: int):
            bt, leaves, _ = self._rest(cache)
            out = []
            for leaf, ax in zip(leaves, batch_axes):
                def one(slot, start, leaf=leaf, ax=ax):
                    row = jax.lax.dynamic_index_in_dim(bt, slot, 0,
                                                       keepdims=False)
                    per = self._gather_slot(leaf, ax, row)
                    sl = jax.lax.dynamic_slice_in_dim(
                        per, start % max_seq, count, axis=ax)
                    return jnp.moveaxis(sl, ax, 0)

                out.append(jax.vmap(one)(slots, starts))
            return out

        return jax.jit(extract, static_argnames=("count",))

    # ------------------------------------------------------------------
    def request_state(self, cache, slot: int) -> List[Any]:
        """Whole-slot state in the CONTIGUOUS layout (gathered through the
        block table) — interchangeable with a contiguous engine's."""
        bt, leaves, _ = self._rest(cache)
        row = bt[slot]
        return [np.asarray(self._gather_slot(leaf, ax, row))
                for leaf, ax in zip(leaves, self.inner.batch_axis)]

    def write_request_state(self, cache, slot: int, state: List[Any]):
        """Scatter a contiguous per-slot state into the slot's mapped
        pages. Blocks left unmapped drop their writes — callers pre-
        allocate pages covering the valid prefix; the dropped tail is
        scrubbed (-1) state anyway."""
        bt, leaves, treedef = self._rest(cache)
        row = bt[slot]
        out = []
        for leaf, ax, s in zip(leaves, self.inner.batch_axis, state):
            safe = jnp.where(row > 0, row, leaf.shape[ax])
            s = jnp.asarray(s, leaf.dtype)
            shp = s.shape[:ax] + (self.nblk, self.page_tokens) + \
                s.shape[ax + 1:]
            # block axis to the front to pair with the page-fronted pool;
            # the page-offset axis stays at ax+1 in both, matching shapes
            paged = jnp.moveaxis(s.reshape(shp), ax, 0)
            dest = jnp.moveaxis(jnp.asarray(leaf), ax, 0)
            dest = dest.at[safe].set(paged, mode="drop")
            out.append(jnp.moveaxis(dest, 0, ax))
        return self._rebuild(bt, out, treedef)

    def scrub_request_state(self, state: List[Any], valid_len: int
                            ) -> List[Any]:
        return self.inner.scrub_request_state(state, valid_len)

    def scrub_slot(self, cache, slot: int, valid_len: int):
        """Mask positions >= valid_len in the slot's mapped pages. Writes
        to shared pages are value-identical (a fully-shared page only
        covers positions < valid_len), and null-page duplicates rewrite
        -1 with -1, so sharing is never corrupted."""
        bt, leaves, treedef = self._rest(cache)
        row = bt[slot]
        out = []
        for leaf, ax, kind in zip(leaves, self.inner.batch_axis,
                                  self.leaf_kind):
            if kind == "attn_pos":
                sub = jnp.take(leaf, row, axis=ax)
                sub = jnp.where(sub >= valid_len, -1, sub)
                idx = (slice(None),) * ax + (row,)
                leaf = jnp.asarray(leaf).at[idx].set(sub)
            out.append(leaf)
        return self._rebuild(bt, out, treedef)

    def clear_slot(self, cache, slot: int):
        """Reset the slot's block-table row to the null page. Page
        disposition (decref / scrub-on-free) is the PagePool's job — the
        engine facade runs it before calling this."""
        bt, leaves, treedef = self._rest(cache)
        return self._rebuild(bt.at[slot].set(0), leaves, treedef)

    def segment_nbytes(self, seg: List[Any], attn_only: bool = False) -> int:
        return self.inner.segment_nbytes(seg, attn_only)

    def prefill_paddable(self, cache, max_seq: int) -> bool:
        return max_seq <= self.max_seq

    # -- device page ops (jitted once; int operands are traced) ----------
    def _copy_page_impl(self, cache, src, dst):
        """Copy-on-extend: duplicate one physical page (all layers)."""
        bt, leaves, treedef = self._rest(cache)
        out = []
        for leaf, ax in zip(leaves, self.inner.batch_axis):
            page = jax.lax.dynamic_index_in_dim(leaf, src, ax,
                                                keepdims=False)
            idx = (slice(None),) * ax + (dst,)
            out.append(leaf.at[idx].set(page))
        return self._rebuild(bt, out, treedef)

    def copy_page(self, cache, src: int, dst: int):
        return self._copy_page_fn(cache, jnp.int32(src), jnp.int32(dst))

    def _scrub_pages_impl(self, cache, pages):
        """Invalidate freed pages' positions so a recycled page can never
        leak stale entries into its next mapper's attention. ``pages`` is
        a fixed-size [nblk] id vector padded with the null page (whose
        positions are -1 already — a no-op rewrite)."""
        bt, leaves, treedef = self._rest(cache)
        out = []
        for leaf, ax, kind in zip(leaves, self.inner.batch_axis,
                                  self.leaf_kind):
            if kind == "attn_pos":
                idx = (slice(None),) * ax + (pages,)
                leaf = leaf.at[idx].set(-1)
            out.append(leaf)
        return self._rebuild(bt, out, treedef)

    def scrub_pages(self, cache, pages: List[int]):
        """Scrub an arbitrary host list of freed page ids (chunked through
        the fixed-size jitted scatter: one trace total)."""
        k = self.nblk
        for i in range(0, len(pages), k):
            chunk = list(pages[i:i + k])
            chunk += [0] * (k - len(chunk))
            cache = self._scrub_pages_fn(
                cache, jnp.asarray(chunk, jnp.int32))
        return cache


# --------------------------------------------------------------------------
# host-side page allocator
# --------------------------------------------------------------------------

class PagePool:
    """Host bookkeeping for the physical page pools: per-AW free lists
    (pages partition across AWs like slots do — a failure domain owns its
    pages), refcounts, and the host mirror of the device block table.

    Page ids are global; page 0 is reserved (never allocated). Refcount
    semantics: an allocated page starts at 1; prefix-cache entries and
    adopting slots each hold one reference; a page returns to its AW's
    free list only when the count hits 0 — the invariant the eviction fix
    (never free a page with refcount > 1) and the property test lean on.
    """

    def __init__(self, num_slots: int, num_aw: int, blocks_per_slot: int,
                 page_tokens: int, pages_per_aw: int = 0):
        from collections import deque
        self.page_tokens = page_tokens
        self.nblk = blocks_per_slot
        self.num_aw = num_aw
        self.slots_per_aw = num_slots // num_aw
        self.pages_per_aw = pages_per_aw or \
            self.slots_per_aw * blocks_per_slot
        self.num_pages = 1 + self.pages_per_aw * num_aw
        self._free = [deque(range(1 + a * self.pages_per_aw,
                                  1 + (a + 1) * self.pages_per_aw))
                      for a in range(num_aw)]
        self.ref = np.zeros(self.num_pages, np.int32)
        self.bt = np.zeros((num_slots, self.nblk), np.int32)
        self.dirty = False   # host bt differs from the device copy

    # ------------------------------------------------------------------
    def aw_of_page(self, pid: int) -> int:
        assert pid > 0
        return (pid - 1) // self.pages_per_aw

    def aw_of_slot(self, slot: int) -> int:
        return slot // self.slots_per_aw

    def free_pages(self, aw: int) -> int:
        return len(self._free[aw])

    def alloc(self, aw: int) -> int:
        """Allocate one page on AW ``aw`` (refcount 1), or -1 if its pool
        is exhausted (caller evicts cached prefixes and retries)."""
        if not self._free[aw]:
            return -1
        pid = self._free[aw].popleft()
        assert self.ref[pid] == 0, pid
        self.ref[pid] = 1
        return pid

    def incref(self, pid: int):
        assert pid > 0 and self.ref[pid] > 0, pid
        self.ref[pid] += 1

    def decref(self, pid: int) -> bool:
        """Drop one reference; True when the page was freed (caller must
        scrub it on device before it can be re-allocated)."""
        assert pid > 0 and self.ref[pid] > 0, pid
        self.ref[pid] -= 1
        if self.ref[pid] == 0:
            self._free[self.aw_of_page(pid)].append(pid)
            return True
        return False

    # ------------------------------------------------------------------
    def map_block(self, slot: int, blk: int, pid: int):
        self.bt[slot, blk] = pid
        self.dirty = True

    def mapped_blocks(self, slot: int) -> int:
        return int((self.bt[slot] > 0).sum())

    def slot_pages(self, slot: int, upto_blocks: int = -1) -> List[int]:
        row = self.bt[slot]
        if upto_blocks >= 0:
            row = row[:upto_blocks]
        return [int(p) for p in row if p > 0]

    def release_slot(self, slot: int) -> List[int]:
        """Unmap the whole slot, decref its pages; returns the pages whose
        refcount hit 0 (to scrub + recycle). Shared pages survive with
        their remaining holders."""
        freed = [pid for pid in self.slot_pages(slot) if self.decref(pid)]
        if self.bt[slot].any():
            self.bt[slot] = 0
            self.dirty = True
        return freed

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        return {"pages_total": self.num_pages - 1,
                "pages_used": int((self.ref[1:] > 0).sum()),
                "pages_shared": int((self.ref[1:] > 1).sum())}

    def check(self) -> None:
        """Allocator invariants (the property test's oracle): every page
        is either free exactly once with refcount 0, or allocated with
        refcount > 0 and on no free list; block tables only reference
        allocated pages."""
        seen: Dict[int, int] = {}
        for aw, fl in enumerate(self._free):
            for pid in fl:
                assert self.aw_of_page(pid) == aw, (pid, aw)
                seen[pid] = seen.get(pid, 0) + 1
        for pid in range(1, self.num_pages):
            if self.ref[pid] == 0:
                assert seen.get(pid, 0) == 1, \
                    f"page {pid} free-count {seen.get(pid, 0)} != 1"
            else:
                assert pid not in seen, f"page {pid} allocated AND free"
        mapped = self.bt[self.bt > 0]
        assert (self.ref[mapped] > 0).all(), "bt references a free page"


# Slot allocation lives with the workers that own the partitions:
# see serving/workers.py (SlotPartition / AttentionWorker / ClusterSlotView).
