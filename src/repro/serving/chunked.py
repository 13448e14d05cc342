"""Chunked-prefill plane: token-budget scheduling + resumable prefill.

The third plane of the serving stack (after the Gateway's admission plane
and the ContinuousBatchScheduler's batching plane), and the first where
performance isolation and failure recovery are the same mechanism: prefill
is no longer an all-at-once batch operation but a budgeted, checkpointable
stream of chunks.

  * **Token-budget iteration planner** — each tick packs at most
    ``chunk_token_budget`` real prompt tokens of prefill work next to the
    decode step (Sarathi-style stall bounding): a long-prompt burst can no
    longer freeze every co-resident decode for a whole-prompt prefill.
  * **O(log) jit keys** — prompt slices are padded to a geometric set of
    chunk shapes (``chunk_min`` · 2^i); the jitted ``prefill_chunk`` call
    always runs over the full slot-partitioned cache, so compilations are
    keyed on the chunk shape alone. Rows not in the chunk (live decode
    slots, other requests) carry position -1 and are untouched.
  * **Resumable streams** — per-request progress lives in
    ``RequestState.prefill_cursor`` and mirrors into the owning
    AttentionWorker's ``prefills`` map (the worker owns its in-flight
    prefill work the way it owns its slots). Chunk-boundary KV segments
    stream to the CheckpointStore through the bulk-segment path
    (CacheLayout.make_slot_range_extractor + KVCheckpointer
    .checkpoint_range), extending the paper's §6.1 incremental decode
    checkpointing to prefill.
  * **Mid-prefill failure recovery** — when an AW dies mid-prefill, the
    request re-enters the Gateway as a recovery entry like any preempted
    decode; restoration injects the committed chunk prefix into a healthy
    slot and resumes prefill *from the cursor* instead of re-prefilling
    from token 0. Only segments past the commit watermark (WRs that died
    with the AW) are recomputed.
  * **Mid-prefill preemption** — planned eviction
    (``engine.preempt_request``, serving/api.py) reuses the same
    ``drop``/``resume`` pair: the stream's pending WRs are *flushed* (not
    dropped — eviction is not a crash), so the resume cursor equals the
    preemption cursor and zero chunk work is recomputed.

Only full-attention cache families expose ``prefill_chunk`` (cache slot ==
absolute position); recurrent/ring-buffer caches keep the exact
whole-prompt scheme in serving/batching.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.serving.telemetry import span


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class ChunkedPrefillStats:
    calls: int = 0                 # jitted chunk invocations
    chunks: int = 0                # (request, chunk) pairs processed
    requests: int = 0              # prefill streams started
    resumed: int = 0               # streams resumed after mid-prefill failure
    real_tokens: int = 0           # true prompt tokens prefilled (incl. any
    #                                recompute after recovery)
    launched_tokens: int = 0       # rows * shape launched per call
    shapes: List[int] = field(default_factory=list)   # distinct shapes used
    prefilled_tokens: Dict[str, int] = field(default_factory=dict)
    restored_tokens: Dict[str, int] = field(default_factory=dict)

    def occupancy(self) -> float:
        return self.real_tokens / self.launched_tokens \
            if self.launched_tokens else 0.0

    def snapshot(self) -> dict:
        return {"calls": self.calls, "chunks": self.chunks,
                "requests": self.requests, "resumed": self.resumed,
                "real_tokens": self.real_tokens,
                "occupancy": self.occupancy(),
                "shapes": sorted(self.shapes)}


@dataclass
class _PrefillJob:
    rid: str
    prompt: np.ndarray
    aw: int
    slot: int
    n_pre: int                     # tokens to prefill (= len(prompt) - 1;
    #                                the last token rides the decode step)


class ChunkedPrefillPlane:
    """Budgeted, resumable prefill over the engine's shared cache."""

    def __init__(self, engine, budget: int, min_chunk: int = 8):
        self.engine = engine
        self.budget = max(1, budget)
        # chunk shapes must fit the cache extent: the biggest shape is the
        # largest power of two <= max_seq, and per-tick takes are capped so
        # _shape_for never rounds past it
        self.max_shape = 1
        while self.max_shape * 2 <= engine.ecfg.max_seq:
            self.max_shape *= 2
        self.min_chunk = max(1, min(min_chunk, self.max_shape))
        self.jobs: Dict[str, _PrefillJob] = {}   # rid -> job, FIFO order
        self.stats = ChunkedPrefillStats()
        self._extract_range = engine.layout.make_slot_range_extractor()

    def set_budget(self, budget: int) -> int:
        """Control-plane actuator: retarget the per-tick token budget. The
        budget is a host int the planner reads fresh each ``plan()`` pass;
        the chunk SHAPE set (pow2 buckets capped at ``max_shape``) never
        changes with it, so adjusting the budget at runtime introduces no
        new jit keys. Returns the clamped value now in effect."""
        self.budget = max(1, int(budget))
        return self.budget

    # ------------------------------------------------------------------
    # admission-side API
    # ------------------------------------------------------------------
    def outstanding_tokens(self) -> int:
        """Prefill tokens admitted but not yet processed — the Gateway's
        token-based admission signal."""
        eng = self.engine
        return sum(j.n_pre - eng.requests[j.rid].prefill_cursor
                   for j in self.jobs.values() if j.rid in eng.requests)

    def start(self, q, aw: int, slot: int, now: float):
        """Open a fresh prefill stream for an admitted request.

        Prefix-cache adoption (serving/prefixcache.py): when placement
        matched a cached prefix (``q.prefix_hit`` > 0), the slot already
        holds its KV — the stale tail is scrubbed instead of clearing the
        slot, the stream starts at ``prefill_cursor = matched_len``, and
        the adopted prefix is re-checkpointed into THIS request's store
        log through the bulk-segment path, so a later crash restores the
        hit just like any committed chunk prefix (the recovery entry
        resumes with the hit intact). A fully-cached prompt skips the
        chunk stream entirely and goes straight to decode."""
        eng = self.engine
        n = len(q.prompt)
        hit = min(getattr(q, "prefix_hit", 0), n - 1)
        if hit > 0:
            # adoption already holds the prefix (by slot reference on a
            # contiguous engine, by shared pages on a paged one): mask the
            # stale tail, keep [0, hit)
            eng._kv_scrub_slot(slot, hit)
        else:
            eng._kv_clear_slot(slot)
        r = eng.make_request_state(q, slot)
        r._aw = aw
        r.t_admit = now
        r.prefilling = True
        r.prefill_cursor = hit
        eng.requests[q.rid] = r
        if eng.ecfg.checkpoint:
            eng.aws[aw].checkpointer.register(q.rid, prompt_len=n)
            if hit > 0:
                # the adopted prefix becomes this request's own
                # checkpointed state — its recovery never depends on the
                # donor entry (whose log was released at adoption)
                eng._bulk_checkpoint(r, 0, hit - 1)
                eng.aws[aw].checkpointer.flush()
        self.stats.requests += 1
        self.stats.prefilled_tokens.setdefault(q.rid, 0)
        if eng.telemetry is not None:
            eng.telemetry.on_prefill_start(q.rid, now, hit, n)
        if hit >= n - 1:
            # whole prompt prefix cached: first decode step emits the
            # first token — warm-turn TTFT is one step
            self._finalize(r)
            return
        self.jobs[q.rid] = _PrefillJob(q.rid, np.asarray(q.prompt), aw, slot,
                                       n_pre=n - 1)
        eng.aws[aw].prefills[q.rid] = hit

    def resume(self, r, aw: int, slot: int, cursor: int, now: float):
        """Re-open a stream after mid-prefill failure recovery: the
        committed prefix [0, cursor) is already restored in the slot; only
        [cursor, n_pre) remains to compute."""
        n_pre = len(r.prompt) - 1
        r.prefill_cursor = cursor
        self.stats.resumed += 1
        if cursor >= n_pre:        # the whole prompt prefix was committed
            self._finalize(r)
            return
        r.prefilling = True
        self.jobs[r.rid] = _PrefillJob(r.rid, np.asarray(r.prompt), aw, slot,
                                       n_pre=n_pre)
        self.engine.aws[aw].prefills[r.rid] = cursor

    def drop(self, rid: str):
        job = self.jobs.pop(rid, None)
        if job is not None:
            self.engine.aws[job.aw].prefills.pop(rid, None)

    def drop_aw(self, aw_id: int):
        """AW crash: its in-flight prefill streams die with it (they are
        re-opened by recovery entries through the Gateway)."""
        for rid in [r for r, j in self.jobs.items() if j.aw == aw_id]:
            del self.jobs[rid]
        self.engine.aws[aw_id].prefills.clear()

    # ------------------------------------------------------------------
    # the iteration planner
    # ------------------------------------------------------------------
    def _shape_for(self, take: int) -> int:
        return min(max(self.min_chunk, _pow2_at_least(take)),
                   self.max_shape)

    def plan(self) -> List[Tuple[_PrefillJob, int]]:
        """Pack (job, take) pairs under the token budget, FIFO over the
        in-flight streams. Every planned job advances by at least one
        token, so a budget smaller than one chunk still makes progress."""
        eng = self.engine
        out: List[Tuple[_PrefillJob, int]] = []
        left = self.budget
        for job in list(self.jobs.values()):
            if left <= 0:
                break
            r = eng.requests.get(job.rid)
            if r is None or r.paused:
                continue
            rem = job.n_pre - r.prefill_cursor
            if rem <= 0:
                continue
            take = min(rem, left, self.max_shape)
            out.append((job, take))
            left -= take
        return out

    def tick(self, now: float) -> int:
        """Run one iteration of budgeted prefill. Returns the number of
        real prompt tokens processed this tick."""
        planned = self.plan()
        if not planned:
            return 0
        by_shape: Dict[int, List[Tuple[_PrefillJob, int]]] = {}
        for job, take in planned:
            by_shape.setdefault(self._shape_for(take), []).append((job, take))
        done = 0
        for shape in sorted(by_shape):
            done += self._run_chunk_call(shape, by_shape[shape], now)
        return done

    # ------------------------------------------------------------------
    # one jitted chunk call (one shape, >= 1 requests)
    # ------------------------------------------------------------------
    def _run_chunk_call(self, shape: int,
                        entries: List[Tuple[_PrefillJob, int]],
                        now: float) -> int:
        eng = self.engine
        rows = eng.ecfg.max_batch
        toks = np.zeros((rows, shape), np.int32)
        pos = np.full((rows, shape), -1, np.int32)
        real = 0
        for job, take in entries:
            r = eng.requests[job.rid]
            c = r.prefill_cursor
            toks[job.slot, :take] = job.prompt[c:c + take]
            pos[job.slot, :take] = np.arange(c, c + take, dtype=np.int32)
            real += take
            # paged: map pages covering the chunk's write range before the
            # dispatch (page allocation is host bookkeeping + one tiny
            # block-table upload — the jitted chunk call is untouched)
            eng._kv_ensure(job.slot, c + take)

        # prefill runs on the request's own (healthy) AW: other AWs'
        # health must not mask its tokens; EW health still applies
        rs_pre = eng.route_state._replace(
            aw_health=jnp.ones_like(eng.route_state.aw_health))
        with span(eng.telemetry, "engine", "chunk.device"):
            if eng.collect_load:
                eng.cache, load = eng._prefill_chunk(
                    eng.params, jnp.asarray(toks), jnp.asarray(pos),
                    eng.cache, rs_pre, capacity=eng.prefill_capacity(real),
                    with_load=True)
                eng.note_dispatch_load(load)
            else:
                eng.cache = eng._prefill_chunk(
                    eng.params, jnp.asarray(toks), jnp.asarray(pos),
                    eng.cache, rs_pre, capacity=eng.prefill_capacity(real))
            # the chunk's KV drain: each stream's new segments
            segs = [self._chunk_segments(job, take, shape)
                    for job, take in entries]

        self.stats.calls += 1
        self.stats.chunks += len(entries)
        self.stats.real_tokens += real
        self.stats.launched_tokens += rows * shape
        if shape not in self.stats.shapes:
            self.stats.shapes.append(shape)

        for (job, take), seg_stack in zip(entries, segs):
            r = eng.requests[job.rid]
            c = r.prefill_cursor
            if seg_stack is not None:
                eng._ck_range(eng.aws[job.aw].checkpointer, job.rid, c,
                              seg_stack, list(job.prompt[c + 1:c + take + 1]))
            r.prefill_cursor = c + take
            eng.aws[job.aw].prefills[job.rid] = r.prefill_cursor
            self.stats.prefilled_tokens[job.rid] = \
                self.stats.prefilled_tokens.get(job.rid, 0) + take
            if eng.telemetry is not None:
                eng.telemetry.on_prefill_chunk(job.rid, now, take, shape)
            if eng.flightrec is not None:
                eng.flightrec.on_chunk(job.rid, now, take, shape, c)
            if r.prefill_cursor >= job.n_pre:
                del self.jobs[job.rid]
                eng.aws[job.aw].prefills.pop(job.rid, None)
                self._finalize(r)
        return real

    def _chunk_segments(self, job: _PrefillJob, take: int, shape: int):
        """The chunk's ``take`` new KV segments of ``job``'s slot, one host
        array per cache leaf (None where checkpointing is off). The
        extractor's static count is the chunk *shape* (bounding jit keys);
        the real segments are sliced out host-side."""
        eng = self.engine
        if not eng.ecfg.checkpoint:
            return None
        start = eng.requests[job.rid].prefill_cursor
        base = min(start, eng.ecfg.max_seq - shape)   # keep it in bounds
        seg_stack = [np.asarray(a)[start - base:start - base + take]
                     for a in self._extract_range(eng.cache, job.slot, base,
                                                  count=shape)]
        eng.note_syncs()
        return seg_stack

    def _finalize(self, r):
        """Prefill complete: hand the request to the decode plane. Like
        the padded whole-prompt scheme, the prompt's last token rides the
        next decode step, which emits the first generated token."""
        n = len(r.prompt)
        r.prefilling = False
        r.pos = n - 1
        r.next_input = int(r.prompt[-1])
        eng = self.engine
        if eng.telemetry is not None:
            eng.telemetry.on_prefill_done(r.rid, eng.telemetry.now)
