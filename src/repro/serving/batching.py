"""Continuous-batching scheduler: batched prefill + interleaved decode.

Sits between the Gateway (admission) and the InferenceEngine facade (device
arrays + jitted step functions). Each tick it:

  1. pulls admitted requests from the Gateway's FIFO queue,
  2. runs prefill for them in *length-bucketed padded batches* — one jitted
     call per bucket instead of one exact-shape call per request, so prompt
     lengths 6/9/12 share a single compilation keyed on (rows, bucket_len),
  3. restores preempted requests (``recovery=True``) from the checkpoint
     store instead of re-prefilling (paper §6.2 per-request restoration),
  4. runs one decode step over all active slots (``step``).

Two prefill schemes, chosen per model from the cache layout:

  * padded (pure full-attention caches) — prefill ``prompt[:-1]`` padded to
    the bucket length; pad entries are scrubbed from the merged slot by
    setting their cache ``pos`` to -1 (the decode kernels mask ``pos < 0``),
    and the prompt's last token is fed through the next *decode* step, which
    naturally interleaves the first generated token with ongoing decodes.
  * exact (ring-buffer / SSM / xLSTM / enc-dec caches, and 1-token prompts)
    — requests of identical prompt length share one unpadded call; the
    first token comes from the prefill's last-position logits. Padding is
    unsafe here because pad tokens would pollute recurrent state or evict
    ring-buffer entries.

Batch rows are padded up to the next power of two (row 0 repeated) so jit
compilations are keyed on O(log max_batch) row counts per bucket length
rather than every batch size ever seen.

When the chunked-prefill plane is enabled (``chunk_token_budget`` > 0,
serving/chunked.py), fresh paddable admissions bypass the whole-prompt
path entirely: their prompts stream through budgeted chunks interleaved
with decode, and recovery of a request preempted *mid-prefill* resumes
the stream from its committed cursor instead of re-prefilling.

Invariant note: pad tokens (length padding and repeated-row padding) are
flagged by a validity mask threaded through ``refe.route``, so they never
compete with real tokens for per-expert capacity ranks, and the prefill
capacity is derived from the REAL token count — a request's routing is
therefore independent of how much padding its batch carries, at any
capacity factor. Co-batched *real* tokens still share capacity cells under
a tight factor, exactly as co-batched decode slots always could.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.checkpoint import _seg_nbytes
from repro.serving.gateway import Gateway, QueuedRequest
from repro.serving.telemetry import span


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class PrefillStats:
    calls: int = 0                 # jitted prefill invocations
    requests: int = 0              # real requests prefilled
    rows: int = 0                  # batch rows launched (incl. row padding)
    real_tokens: int = 0           # true prompt tokens processed
    padded_tokens: int = 0         # rows * bucket_len launched
    batch_sizes: List[int] = field(default_factory=list)

    def occupancy(self) -> float:
        """Fraction of launched prefill FLOPs spent on real prompt tokens."""
        return self.real_tokens / self.padded_tokens if self.padded_tokens \
            else 0.0

    def mean_batch(self) -> float:
        return float(np.mean(self.batch_sizes)) if self.batch_sizes else 0.0

    def snapshot(self) -> dict:
        return {"calls": self.calls, "requests": self.requests,
                "occupancy": self.occupancy(),
                "mean_batch": self.mean_batch()}


class ContinuousBatchScheduler:
    """Drives admission, bucketed prefill, restoration, and decode over the
    engine's shared device state."""

    def __init__(self, engine, gateway: Gateway, bucket: int = 16):
        self.engine = engine
        self.gateway = gateway
        self.bucket = max(1, bucket)
        self.stats = PrefillStats()

    # ------------------------------------------------------------------
    # admission: gateway pop -> prefill/restore -> installed RequestState
    # ------------------------------------------------------------------
    def admit(self, now: float = 0.0) -> List[str]:
        """Admit as many queued requests as placement allows. Returns the
        rids installed this tick (fresh and recovered)."""
        eng = self.engine
        admitted = self.gateway.admit(now)
        fresh: List[Tuple[QueuedRequest, int, int]] = []
        installed: List[str] = []
        for q, aw, slot in admitted:
            if q.recovery:
                self._install_recovery(q, aw, slot, now)
            elif eng.chunked is not None and eng.prefill_paddable and \
                    len(q.prompt) >= 2:
                # chunked-prefill plane: the prompt streams through
                # budgeted chunks on subsequent ticks
                eng.chunked.start(q, aw, slot, now)
            else:
                fresh.append((q, aw, slot))
            installed.append(q.rid)
        for group in self._bucket_groups(fresh):
            self._prefill_group(group, now)
        return installed

    # -- grouping -----------------------------------------------------------
    def _bucket_groups(self, fresh):
        """Split fresh admissions into prefill groups: (padded, bucket_len)
        for the padded scheme, (exact, prompt_len) otherwise. Groups are
        capped at max_batch rows."""
        eng = self.engine
        groups: Dict[Tuple[bool, int], list] = {}
        for q, aw, slot in fresh:
            n = len(q.prompt)
            if eng.prefill_paddable and n >= 2:
                lb = -((n - 1) // -self.bucket) * self.bucket  # ceil bucket
                key = (True, lb)
            else:
                key = (False, n)
            groups.setdefault(key, []).append((q, aw, slot))
        out = []
        cap = eng.ecfg.max_batch
        for key, entries in sorted(groups.items(), key=lambda kv: kv[0]):
            for i in range(0, len(entries), cap):
                out.append((key, entries[i:i + cap]))
        return out

    # -- prefill ------------------------------------------------------------
    def _prefill_group(self, group, now: float):
        (padded, length), entries = group
        eng = self.engine
        n_real = len(entries)
        rows = _next_pow2(n_real)
        toks = np.zeros((rows, length), np.int32)
        pre_lens = []
        for i, (q, _, _) in enumerate(entries):
            pre = q.prompt[:-1] if padded else q.prompt
            toks[i, :len(pre)] = pre
            pre_lens.append(len(pre))
        for i in range(n_real, rows):           # row padding: repeat row 0
            toks[i] = toks[0]

        batch = {"tokens": jnp.asarray(toks)}
        capacity = None
        if eng.prefill_masked:
            # pad-free dispatch: flag real tokens (length pads AND repeated
            # row pads are excluded from expert-capacity competition) and
            # size capacity from the real token count
            mask = np.zeros((rows, length), bool)
            for i, n_pre in enumerate(pre_lens):
                mask[i, :n_pre] = True
            batch["mask"] = jnp.asarray(mask)
            capacity = eng.prefill_capacity(sum(pre_lens))
        if eng.cfg.is_encdec:
            frames = []
            for q, _, _ in entries:
                f = q.frames if q.frames is not None else np.zeros(
                    (eng.cfg.encoder_seq, eng.cfg.d_model), np.float32)
                frames.append(f)
            for _ in range(n_real, rows):
                frames.append(frames[0])
            batch["frames"] = jnp.asarray(np.stack(frames))

        # prefill runs on the request's own (healthy) AW: other AWs' health
        # must not mask its tokens; EW health still applies (shadow reroute)
        rs_pre = eng.route_state._replace(
            aw_health=jnp.ones_like(eng.route_state.aw_health))
        kw = {"capacity": capacity} if eng.prefill_masked else {}
        if eng.collect_load:
            last_logits, req_cache, load = eng._prefill(
                eng.params, batch, rs_pre, max_seq=eng.ecfg.max_seq,
                with_load=True, **kw)
            eng.note_dispatch_load(load)
        else:
            last_logits, req_cache = eng._prefill(
                eng.params, batch, rs_pre, max_seq=eng.ecfg.max_seq, **kw)
        firsts = None
        if not padded:
            # exact scheme: the first token comes from the prefill's last-
            # position logits, sampled on device with the same counter-based
            # head as decode (key pos = last prompt position, the position
            # a decode step would have consumed)
            firsts = eng.decode_plane.sample_rows(
                last_logits, [q for q, _, _ in entries],
                [len(q.prompt) - 1 for q, _, _ in entries])
            eng.note_syncs()

        self.stats.calls += 1
        self.stats.requests += n_real
        self.stats.rows += rows
        self.stats.real_tokens += sum(pre_lens)
        self.stats.padded_tokens += rows * length
        self.stats.batch_sizes.append(n_real)

        # the prefill builds a contiguous per-request cache whatever the
        # engine's layout: its rows are read with the contiguous layout
        prefill_layout = getattr(eng.layout, "inner", eng.layout)
        for i, (q, aw, slot) in enumerate(entries):
            state = prefill_layout.request_state(req_cache, i)
            if padded and pre_lens[i] < length:
                state = eng.layout.scrub_request_state(state, pre_lens[i])
            # paged engines map pages covering the prefilled prefix before
            # the scatter (writes beyond the mapped blocks are scrubbed
            # padding and drop harmlessly)
            eng._kv_ensure(slot, pre_lens[i])
            eng.cache = eng.layout.write_request_state(eng.cache, slot, state)
            first = int(firsts[i]) if not padded else None
            self._install_fresh(q, aw, slot, now, padded=padded, first=first,
                                n_prefilled=pre_lens[i])

    def _install_fresh(self, q: QueuedRequest, aw: int, slot: int,
                       now: float, *, padded: bool, first: Optional[int],
                       n_prefilled: int):
        eng = self.engine
        n = len(q.prompt)
        st = eng.make_request_state(q, slot)
        st._aw = aw
        st.t_admit = now
        if padded:
            # prompt's last token rides the next decode step; the first
            # generated token is sampled there (true continuous batching)
            st.pos = n - 1
            st.next_input = int(q.prompt[-1])
        else:
            st.tokens = [int(first)]
            st.pos = n
            st.next_input = int(first)
            st.t_first_token = now
            if len(st.tokens) >= st.max_new:   # max_new=1: done at prefill
                st.done = True
                st.t_done = now
        eng.requests[q.rid] = st
        if eng.telemetry is not None:
            eng.telemetry.on_whole_prefill(
                q.rid, now, n, "padded" if padded else "exact")

        if eng.ecfg.checkpoint:
            ck = eng.aws[aw].checkpointer
            ck.register(q.rid, prompt_len=n)
            if n_prefilled > 0:
                slots = jnp.full((n_prefilled,), slot, jnp.int32)
                tk = jnp.arange(n_prefilled, dtype=jnp.int32)
                stacked = [np.asarray(a)
                           for a in eng._extract(eng.cache, slots, tk)]
                eng.note_syncs()
                eng.note_checkpoint(n_prefilled, _seg_nbytes(stacked))
                for t in range(n_prefilled):
                    seg = [a[t] for a in stacked]
                    # token_value = next decode input after position t
                    tv = int(q.prompt[t + 1]) if t + 1 < n else int(first)
                    ck.checkpoint_token(q.rid, t, seg, token_value=tv)
            ck.flush()

    # -- per-request restoration (recovery admissions) ----------------------
    def _install_recovery(self, q: QueuedRequest, aw: int, slot: int,
                          now: float):
        """§6.2: inject the committed KV prefix into the new slot and rewind
        the request to the committed token. A request preempted mid-prefill
        re-enters the chunked plane with its cursor at the commit watermark
        — only the uncommitted tail of the prompt is recomputed, never the
        whole prompt."""
        eng = self.engine
        r = eng.requests.get(q.rid)
        if r is None:              # released while waiting for recovery
            eng.aws[aw].slots.release(slot)
            return
        tel = eng.telemetry
        with span(tel, "recovery", "recovery.restore", rid=q.rid) as sp:
            with span(tel, "recovery", "restore.read"):
                committed, tok_val, segs = eng.store.restore_request(q.rid)
            with span(tel, "recovery", "restore.write"):
                eng._kv_clear_slot(slot)
                if segs:
                    # paged: map pages covering the restored prefix first
                    # — the committed segments then scatter through the
                    # block table
                    eng._kv_ensure(slot, max(segs) + 1)
                eng.cache = eng.layout.write_token_segments(
                    eng.cache, slot, list(segs), list(segs.values()))
            if tel is not None:
                nbytes = sum(_seg_nbytes(seg) for seg in segs.values())
                sp.args.update(segments=len(segs), bytes=nbytes)
                tel.registry.inc("restore.segments", len(segs))
                tel.registry.inc("restore.bytes", nbytes)
                tel.registry.inc("restore.cache_writes",
                                 -(-len(segs) // eng.layout.block_tokens))

        r.slot = slot
        r._aw = aw
        r.paused = False
        r.queued_for_recovery = False
        r.t_admit = now
        eng.store.reassign(q.rid, aw)
        # re-bind sampling to the (possibly different) recovery slot; the
        # counter-based key is slot-independent, so the replayed stream is
        # bit-identical wherever the request lands
        eng.decode_plane.bind(r)
        if eng.telemetry is not None:
            eng.telemetry.on_restore(q.rid, now, len(segs), r.prefilling)
        if eng.flightrec is not None:
            eng.flightrec.on_restore(q.rid, now, len(segs), r.prefilling)

        if r.prefilling:
            # mid-prefill preemption: resume the chunk stream after the
            # restored prefix (cursor = committed + 1; committed may be -1
            # when the failure hit before any chunk was committed)
            assert eng.chunked is not None
            eng.chunked.stats.restored_tokens[q.rid] = \
                eng.chunked.stats.restored_tokens.get(q.rid, 0) + len(segs)
            eng.chunked.resume(r, aw, slot, committed + 1, now)
            return

        n_prompt = len(r.prompt)
        n_gen = max(0, committed + 2 - n_prompt)
        r.tokens = r.tokens[:n_gen]
        r.pos = committed + 1
        if committed + 1 < n_prompt:
            r.next_input = int(r.prompt[committed + 1])
        elif tok_val >= 0:
            r.next_input = int(tok_val)
        elif r.tokens:
            r.next_input = int(r.tokens[-1])

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def step(self, now: Optional[float] = None) -> Dict[str, List[int]]:
        """One iteration: an admission pass when anything is waiting (so
        Client-submitted and preempted requests re-enter without an
        external serving loop), deadline accounting, a budgeted slice of
        chunked prefill (when the plane is on), then one decode *segment*
        over all active slots — ``decode_segment_len`` device steps per
        dispatch (1 = per-step cadence). Returns {rid: new_tokens}."""
        eng = self.engine
        t_now = now if now is not None else float(eng.steps)
        with span(eng.telemetry, "engine", "step"):
            return self._step(t_now)

    def _step(self, t_now: float) -> Dict[str, List[int]]:
        eng = self.engine
        tel = eng.telemetry
        if eng.controller is not None:
            # control-plane decision pass BEFORE admission: scale/rebalance
            # requests land on the orchestrator's virtual clock and the
            # chunk budget is set before this tick's planner slice runs
            with span(tel, "engine", "step.hooks"):
                eng.controller.tick(t_now)
        if self.gateway.depth():
            with span(tel, "engine", "step.admit"):
                self.admit(t_now)
        with span(tel, "engine", "step.hooks"):
            eng.check_deadlines(t_now)
            if eng.flightrec is not None:
                # forensics plane: drain the bus through the recorder's
                # own cursor, fingerprint when due, advance the watchdogs
                # — host-side only, no effect on anything below
                eng.flightrec.tick(t_now)
        if eng.chunked is not None:
            with span(tel, "engine", "step.chunk"):
                eng.chunked.tick(t_now)
        act = eng.active_requests()
        if not act:
            return {}
        if eng.decode_plane.seg_len > 1:
            return self._step_segment(act, t_now)
        return self._step_single(act, t_now)

    def _step_single(self, act, t_now: float) -> Dict[str, List[int]]:
        """Per-step cadence (decode_segment_len=1): one jitted decode
        dispatch + device sampling; only the [B] token vector crosses to
        the host — the [B,V] logits never do."""
        eng = self.engine
        tel = eng.telemetry
        with span(tel, "engine", "step.decode"):
            tokens = np.zeros((eng.ecfg.max_batch,), np.int32)
            # inactive rows carry pos -1: their cache writes are dropped,
            # so a decode step can never clobber a slot that is
            # mid-chunked-prefill
            pos = np.full((eng.ecfg.max_batch,), -1, np.int32)
            for r in act:
                tokens[r.slot] = r.next_input
                pos[r.slot] = r.pos
                # paged: the step writes KV at r.pos — its page must be
                # mapped
                eng._kv_ensure(r.slot, r.pos + 1)
            pos_dev = jnp.asarray(pos)
            with span(tel, "engine", "decode.device"):
                if eng.collect_load:
                    logits, eng.cache, load = eng._decode(
                        eng.params, jnp.asarray(tokens), pos_dev, eng.cache,
                        eng.route_state, capacity=eng.decode_capacity,
                        with_load=True)
                    eng.note_dispatch_load(load)
                else:
                    logits, eng.cache = eng._decode(
                        eng.params, jnp.asarray(tokens), pos_dev, eng.cache,
                        eng.route_state, capacity=eng.decode_capacity)
                # sampling head stays on device (counter-based,
                # slot-indexed params); only the [B] token vector crosses
                # to the host — the [B,V] logits never do
                toks = np.asarray(eng.decode_plane.sample(logits, pos_dev))
            eng.note_syncs()
            self.gateway.stats.host_syncs += 1

            out: Dict[str, List[int]] = {}
            t_log = t_now
            for r in act:
                nxt = int(toks[r.slot])
                r.pos += 1               # decode wrote KV at r.pos - 1
                r.tokens.append(nxt)
                r.next_input = nxt
                if r.t_first_token < 0:
                    r.t_first_token = t_log
                out[r.rid] = [nxt]
                if len(r.tokens) >= r.max_new or \
                        r.pos >= eng.ecfg.max_seq - 1:
                    r.done = True
                    r.t_done = t_log

        with span(tel, "engine", "step.checkpoint"):
            ck_reqs = [r for r in act
                       if eng.ecfg.checkpoint and eng.aws[r.aw].alive]
            if ck_reqs:
                # single batched device->host gather for all requests'
                # segments: the KV each one's decode wrote at pos - 1
                slots = jnp.asarray([r.slot for r in ck_reqs], jnp.int32)
                tk = jnp.asarray([r.pos - 1 for r in ck_reqs], jnp.int32)
                with span(tel, "engine", "checkpoint.device"):
                    stacked = [np.asarray(a)
                               for a in eng._extract(eng.cache, slots, tk)]
                eng.note_syncs()
                eng.note_checkpoint(len(ck_reqs), _seg_nbytes(stacked))
                for i, r in enumerate(ck_reqs):
                    eng.aws[r.aw].checkpointer.checkpoint_token(
                        r.rid, r.pos - 1, [a[i] for a in stacked],
                        token_value=r.next_input)
            for w in eng.aws:
                w.checkpointer.flush()
        eng.steps += 1
        return out

    def _step_segment(self, act, t_now: float) -> Dict[str, List[int]]:
        """Segmented cadence (decode_segment_len>1): ONE lax.scan dispatch
        runs up to seg_len decode+sample steps on device; the token ring
        drains to the host once, and each request's newly written KV range
        streams to the checkpoint store through the bulk-segment path
        (§6.1), so segment boundaries ARE checkpoint boundaries — a crash
        mid-segment rewinds at most seg_len tokens via the §6.2 restore."""
        eng = self.engine
        tel = eng.telemetry
        seg_len = eng.decode_plane.seg_len
        with span(tel, "engine", "step.decode"):
            with span(tel, "engine", "decode.device"):
                ring, loads = eng.decode_plane.run_segment(act, seg_len)
            eng.note_syncs()
            self.gateway.stats.host_syncs += 1     # the per-segment drain
            if eng.collect_load:
                for i in range(seg_len):
                    eng.note_dispatch_load(loads[i])

            out: Dict[str, List[int]] = {}
            max_seq = eng.ecfg.max_seq
            ck_items = []
            for r in act:
                # the device stop mask and this count are the same
                # formula: steps until max_new or the cache ceiling,
                # capped by seg_len
                n_take = max(0, min(seg_len, r.max_new - len(r.tokens),
                                    (max_seq - 1) - r.pos))
                col = ring[:, r.slot]
                start = r.pos
                toks = [int(c) for c in col[:n_take]]
                assert all(c >= 0 for c in toks), \
                    f"{r.rid}: ring drained an inactive step"
                for nxt in toks:
                    r.pos += 1
                    r.tokens.append(nxt)
                    r.next_input = nxt
                if toks and r.t_first_token < 0:
                    r.t_first_token = t_now
                out[r.rid] = toks
                if toks and eng.ecfg.checkpoint and eng.aws[r.aw].alive:
                    ck_items.append((r, start, len(toks)))
                if len(r.tokens) >= r.max_new or r.pos >= max_seq - 1:
                    r.done = True
                    r.t_done = t_now
        with span(tel, "engine", "step.checkpoint"):
            if ck_items:
                # checkpoint_range over exactly the segment's KV writes —
                # one multi-slot device gather for every request in the
                # segment
                eng._bulk_checkpoint_group(ck_items)
            for w in eng.aws:
                w.checkpointer.flush()
        eng.steps += 1
        return out
