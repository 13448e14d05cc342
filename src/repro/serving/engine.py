"""Tarragon inference engine — a thin facade over the layered serving stack.

Layers (paper Fig. 5; see ARCHITECTURE.md for the full map):

  * ``Gateway``        (serving/gateway.py)  — admission, FIFO waiting
    queue, pluggable AW placement policy.
  * ``AttentionWorker`` / ``ExpertWorker`` (serving/workers.py) — per-worker
    failure domains: each AW owns its slot partition + checkpoint stream,
    each EW its liveness; ``fail``/``provision`` are worker methods.
  * ``ContinuousBatchScheduler`` (serving/batching.py) — length-bucketed
    batched prefill, per-request restoration for recovery re-admissions,
    and the shared decode step.

The engine itself owns only the *device-side* arrays of the single-process
simulation (params, route state, the slot-partitioned cache pytree) plus
the jitted step functions, and re-exports the historical API
(``submit``/``step``/``generate``/``fail_*``/``provision_*``) so tests,
benchmarks, and the orchestrator keep working unchanged.

Failure API (used by the orchestrator and by tests):
  * ``fail_aw(a)``   — AW a crashes: its slots are lost and its requests
    pause; they re-enter through the Gateway and restore from the
    checkpoint store onto healthy AWs (per-request restoration, §6.2).
  * ``fail_ew(e)``   — EW e crashes: the ERT immediately resolves its
    experts to shadow slots (AW-side self-healing); nothing else changes.
  * ``provision_*`` — background capacity restoration (§5.4).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import selfheal
from repro.core.checkpoint import CheckpointStore, _seg_nbytes
from repro.core.orchestrator import WorkerEvent
from repro.core.placement import ExpertPlacementManager, PlacementPlan
from repro.core.refe import RouteState
from repro.models import get_model
from repro.serving import flightrec
from repro.serving.api import (PREEMPTIBLE_CLASSES, STANDARD, Client,
                               SamplingParams)
from repro.serving.batching import ContinuousBatchScheduler
from repro.serving.chunked import ChunkedPrefillPlane
from repro.serving.controller import ServingController
from repro.serving.decode_loop import DecodeLoopPlane
from repro.serving.gateway import Gateway, QueuedRequest
from repro.serving.kvcache import CacheLayout, PagedCacheLayout, PagePool
from repro.serving.prefixcache import PrefixCachePlane
from repro.serving.telemetry import EventBus, TelemetryPlane, span
from repro.serving.workers import (AttentionWorker, ClusterSlotView,
                                   ExpertWorker)


@dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 96
    num_aw: int = 2
    num_ew: int = 2
    max_ew: int = 0                # elastic EW pool ceiling (spare worker
    #                                ids the Orchestrator can scale out
    #                                into; 0 = num_ew, i.e. no spares)
    tarragon: bool = True          # False = MegaScale-style static binding
    checkpoint: bool = True
    checkpoint_reorder: int = 0    # test hook: reorder window for WR arrival
    greedy: bool = True
    temperature: float = 1.0       # sampling temperature (greedy=False)
    top_k: int = 0                 # 0 = full distribution (greedy=False)
    sample_seed: int = 0
    decode_segment_len: int = 1    # decode steps per jitted lax.scan
    #                                segment (serving/decode_loop.py);
    #                                1 = per-step dispatch, today's cadence.
    #                                >1 drains tokens to the host once per
    #                                segment and checkpoints the segment
    #                                through the bulk range path; a failure
    #                                mid-segment rewinds at most this many
    #                                tokens (transformer family only)
    capacity_factor_decode: float = 0.0  # 0 = use model default
    placement: str = "least_loaded"      # Gateway placement policy
    prefill_bucket: int = 16             # padded-prefill length bucket
    # ---- chunked-prefill plane (serving/chunked.py) ----------------------
    chunk_token_budget: int = 0          # real prefill tokens per tick
    #                                      (0 = whole-prompt prefill path)
    chunk_min: int = 8                   # smallest chunk shape; shapes are
    #                                      chunk_min * 2^i (O(log) jit keys)
    prefill_token_cap: int = 0           # Gateway admission cap on
    #                                      outstanding prefill tokens (0 =
    #                                      slot-bound admission only)
    preempt: bool = True                 # blocked interactive heads may
    #                                      checkpoint-and-evict a batch
    #                                      victim (preempt-and-requeue)
    victim_policy: str = "remaining_work"  # preemption victim selection:
    #                                      "remaining_work" (most tokens
    #                                      left, prefill debt included) or
    #                                      "youngest" (latest arrival —
    #                                      the pre-PR-5 behavior)
    # ---- prefix-cache plane (serving/prefixcache.py) ---------------------
    prefix_cache_slots: int = 0          # per-AW cached-prefix slot budget
    #                                      (0 = plane off; requires the
    #                                      chunked plane)
    prefix_cache_tokens: int = 0         # per-AW cached-token budget
    #                                      (0 = slot budget only)
    prefix_min_match: int = 4            # shortest prefix worth adopting
    #                                      (adoption truncates the entry —
    #                                      a trivial coincidental match
    #                                      must not eat a long prefix)
    prefix_restore: bool = True          # restore a dead AW's cached
    #                                      prefixes from the checkpoint
    #                                      store onto healthy AWs
    # ---- paged KV plane (serving/kvcache.py) -----------------------------
    kv_page_tokens: int = 0              # physical KV page extent in tokens
    #                                      (0 = contiguous per-slot cache;
    #                                      >0 needs a pure full-attention
    #                                      cache family and must divide
    #                                      max_seq). Paged slots map pages
    #                                      through a block table; shared
    #                                      prefixes reference the SAME
    #                                      physical pages (refcounted,
    #                                      copy-on-extend at the boundary)
    kv_pages: int = 0                    # per-AW physical page budget
    #                                      (0 = parity with the contiguous
    #                                      footprint: slots_per_aw * nblk;
    #                                      smaller budgets trade capacity
    #                                      against prefix-sharing wins)
    prefix_global_index: bool = False    # lift the per-AW radix indexes to
    #                                      one gateway-level index routing
    #                                      any arrival to its best-match AW
    #                                      cluster-wide (paged mode only)
    prefix_migrate: bool = False         # when the best-match AW cannot
    #                                      take the hit (full or dead),
    #                                      replay the hot prefix onto a
    #                                      healthy AW through the existing
    #                                      checkpoint-store bulk path
    # ---- telemetry plane (serving/telemetry.py) --------------------------
    telemetry: bool = True               # metrics registry + span tracing
    #                                      + stall attribution (host-side
    #                                      only: on/off is bit-identical
    #                                      and trace-count-identical)
    stall_threshold: float = 0.25        # TTFT/TBT gap (virtual s) above
    #                                      which per-cause attribution runs
    hist_buckets_per_decade: int = 32    # streaming-histogram resolution
    #                                      (quantile error = one bucket,
    #                                      ~7.5% at 32)
    trace_export_path: str = ""          # write the Perfetto/Chrome trace
    #                                      here at run finalize ("" = off)
    # ---- control plane (serving/controller.py) ---------------------------
    controller: str = "off"              # "off" (shipped default: every
    #                                      knob stays static, byte-identical
    #                                      to pre-controller behavior) |
    #                                      "on" (one decision pass per tick)
    ctl_autoscale: bool = True           # policy 1: EW pool sizing from
    #                                      queue-depth EMA watermarks
    ctl_rebalance: bool = True           # policy 2: trajectory-triggered
    #                                      rebalance + weighted split plans
    ctl_chunk_budget: bool = True        # policy 3: SLO-headroom-adaptive
    #                                      chunk budget
    ctl_queue_high: float = 3.0          # scale-out watermark (queue EMA)
    ctl_queue_low: float = 0.25          # scale-in watermark (queue EMA;
    #                                      pool must also be idle + above
    #                                      its boot size)
    ctl_scale_dwell: float = 0.0         # debounce between scale decisions
    #                                      (0 = auto: T_w + 2*T_push of the
    #                                      attached orchestrator)
    ctl_headroom: float = 0.25           # interactive deadline headroom
    #                                      (virtual s) under which the
    #                                      chunk budget shrinks
    ctl_budget_min: int = 0              # adaptive-budget floor (0 = auto:
    #                                      max(min_chunk, base/4))
    ctl_budget_max: int = 0              # adaptive-budget ceiling (0 =
    #                                      auto: 4x the configured base)
    ctl_deadline_risk: float = 0.1       # head deadline headroom (virtual
    #                                      s) below which the preemption
    #                                      gate opens (victim_policy=
    #                                      "controller" only)
    ctl_kv_weight: float = 1.0           # victim pricing: weight on the
    #                                      resident/exclusive-KV value
    #                                      subtracted from remaining work
    # ---- forensics plane (serving/flightrec.py) --------------------------
    flight_recorder: bool = True         # black-box FlightRecorder riding
    #                                      the EventBus (host-side only:
    #                                      on/off is bit-identical and
    #                                      trace-count-identical)
    flight_capacity: int = 4096          # ring size for records /
    #                                      submissions / outputs (oldest
    #                                      drop past this; drops counted)
    flight_fingerprint_every: float = 0.5  # virtual-clock period between
    #                                      engine-state fingerprints
    #                                      (0 = only on dump)
    flight_autodump: str = ""            # write a postmortem bundle here
    #                                      on the first failure detection
    #                                      or watchdog trip ("" = off)
    watchdogs: bool = False              # continuous health watchdogs:
    #                                      leak detector, stall-regression
    #                                      detector, invariant probes
    wd_interval: float = 0.25            # watchdog sampling interval
    #                                      (virtual s); watermarks close
    #                                      once per interval
    wd_window: int = 8                   # sliding window length
    #                                      (intervals) for trend tests
    wd_leak_min_drop: int = 2            # free-list watermark drop across
    #                                      a full window that counts as a
    #                                      leak (monotone trend required)
    wd_stall_factor: float = 2.0         # windowed TTFT/TBT p99 multiple
    #                                      over baseline that trips the
    #                                      stall-regression detector
    wd_settle: float = 1.0               # quiet time after a disturbance
    #                                      (fault/scale/preempt) before
    #                                      leak/stall judgments resume


@dataclass
class RequestState:
    rid: str
    slot: int
    prompt: np.ndarray
    max_new: int
    tokens: List[int] = field(default_factory=list)  # generated tokens
    pos: int = 0                  # next position to write
    next_input: int = -1          # token id the next decode step consumes
    done: bool = False
    paused: bool = False          # owning AW died; awaiting re-admission
    queued_for_recovery: bool = False
    prefilling: bool = False      # prompt still streaming through the
    #                               chunked-prefill plane (no decode yet)
    prefill_cursor: int = 0       # prompt tokens already written to cache
    # typed request-lifecycle fields (serving/api.py)
    slo_class: str = STANDARD
    deadline: Optional[float] = None   # virtual-clock first-token deadline
    completion_deadline: Optional[float] = None  # last-token deadline
    sampling: Optional[SamplingParams] = None
    session: Optional[str] = None
    preemptions: int = 0          # planned evictions survived
    cancelled: bool = False
    deadline_flagged: bool = False
    completion_flagged: bool = False   # completion overrun already counted
    prefix_hit: int = 0           # prompt tokens adopted from the prefix
    #                               cache at admission (0 = cold)
    # virtual-clock timeline (all on the serving loop's clock)
    t_enqueue: float = 0.0
    t_admit: float = -1.0
    t_first_token: float = -1.0
    t_done: float = -1.0

    _aw: int = -1

    @property
    def aw(self) -> int:
        return self._aw

    @property
    def state(self) -> str:
        """Lifecycle state machine: queued -> placed -> prefilling ->
        decoding -> {done, preempted, cancelled} (queued is pre-admission,
        i.e. before a RequestState exists; preempted is transient — the
        request re-enters via the recovery path)."""
        if self.cancelled:
            return "cancelled"
        if self.done:
            return "done"
        if self.paused or self.queued_for_recovery:
            return "preempted"
        if self.prefilling:
            return "prefilling"
        return "decoding" if self.tokens else "placed"

    @property
    def ttft(self) -> float:
        """Virtual-clock time-to-first-token (enqueue -> first token)."""
        return self.t_first_token - self.t_enqueue \
            if self.t_first_token >= 0 else -1.0


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig, key=None):
        self.cfg = cfg
        self.ecfg = ecfg
        key = key if key is not None else jax.random.PRNGKey(0)
        # host copy of the init key, pinned so a postmortem bundle can
        # rebuild THIS engine exactly (serving/flightrec.py)
        self.init_key_data = flightrec.key_host_data(key)
        self.api = get_model(cfg, num_aw=ecfg.num_aw, num_ew=ecfg.num_ew,
                             tarragon=ecfg.tarragon)
        # jitted, so every leaf is drawn straight into the model dtype: the
        # cast fuses with the draw and no float32 copy of the tree is live
        self.params = jax.jit(self.api.init_params)(key)
        self.route_state: RouteState = self.api.init_route_state()
        # ---- KV plane: contiguous per-slot cache, or paged block tables ---
        # Paged mode (kv_page_tokens > 0) swaps the layout, not the model:
        # the per-layer pools are the ordinary contiguous cache built with
        # batch=num_pages, max_seq=page_tokens, plus one [B, nblk] block
        # table the transformer stack keys its paged attention variants on.
        # The engine is paged or contiguous for life — one trace set either
        # way, and the decision never leaks into jit keys.
        assert ecfg.max_batch % ecfg.num_aw == 0
        self.pages: Optional[PagePool] = None
        if ecfg.kv_page_tokens > 0:
            pt = ecfg.kv_page_tokens
            assert ecfg.max_seq % pt == 0, (
                f"kv_page_tokens={pt} must divide max_seq={ecfg.max_seq}")
            assert not getattr(cfg, "sliding_window", 0), (
                "paged KV requires all-global attention (the block-table "
                "gather has no ring-buffer wrap); set sliding_window=0")
            self.layout = PagedCacheLayout(self.api.init_cache, pt,
                                           ecfg.max_seq)
            self.pages = PagePool(ecfg.max_batch, ecfg.num_aw,
                                  self.layout.nblk, pt,
                                  pages_per_aw=ecfg.kv_pages)
            self.cache = self.layout.make_cache(
                self.api.init_cache, ecfg.max_batch, self.pages.num_pages)
        else:
            self.cache = self.api.init_cache(ecfg.max_batch, ecfg.max_seq)
            self.layout = CacheLayout(self.api.init_cache)
        assert self.pages is not None or not (
            ecfg.prefix_global_index or ecfg.prefix_migrate), (
            "prefix_global_index / prefix_migrate require the paged KV "
            "plane (kv_page_tokens > 0)")
        self.store = CheckpointStore()

        # ---- worker pool: per-worker failure domains ----------------------
        assert ecfg.max_batch % ecfg.num_aw == 0
        per_aw = ecfg.max_batch // ecfg.num_aw
        self.aws = [AttentionWorker(a, a * per_aw, (a + 1) * per_aw,
                                    self.store,
                                    reorder_window=ecfg.checkpoint_reorder)
                    for a in range(ecfg.num_aw)]
        if self.pages is not None:
            for w in self.aws:
                w.page_pool = self.pages
        max_ew = max(ecfg.max_ew or ecfg.num_ew, ecfg.num_ew)
        self.ews = [ExpertWorker(e, member=e < ecfg.num_ew)
                    for e in range(max_ew)]
        self.slots = ClusterSlotView(self.aws, ecfg.max_batch)

        # ---- elastic expert plane (core/placement.py) ---------------------
        # versioned placement plans + load telemetry; the manager's arrays
        # ride RouteState, so every plan install is trace-free
        self.placement_mgr: Optional[ExpertPlacementManager] = None
        self.plan_log: List[WorkerEvent] = []
        if ecfg.tarragon and self.api.placement is not None:
            self.placement_mgr = ExpertPlacementManager(
                self.api.placement, ecfg.num_ew, max_ew=max_ew)
            self.route_state = self.route_state._replace(
                ew_health=jnp.asarray(self.placement_mgr.ew_member_mask()),
                **self._plan_arrays(self.placement_mgr.plan))
        self.collect_load = (self.placement_mgr is not None and
                             self.api.reports_load)

        # ---- request plane ------------------------------------------------
        self.gateway = Gateway(self.aws, policy=ecfg.placement)
        self.scheduler = ContinuousBatchScheduler(
            self, self.gateway, bucket=ecfg.prefill_bucket)
        # ---- telemetry plane (serving/telemetry.py) -----------------------
        # publish-at-emission event bus (multi-consumer, cursor-based) +
        # optional metrics/span/attribution plane. Both are host-side
        # bookkeeping only: no device arrays, no jax calls.
        self.bus = EventBus()
        self.gateway.attach_bus(self.bus)
        self.telemetry: Optional[TelemetryPlane] = \
            TelemetryPlane(self) if ecfg.telemetry else None
        self.gateway.telemetry = self.telemetry
        self.requests: Dict[str, RequestState] = {}
        # typed request-lifecycle plane (serving/api.py): preemption hook,
        # lifecycle event timeline, release listeners for handles
        if ecfg.preempt:
            self.gateway.preemptor = self._preempt_for
        self.request_log: List[WorkerEvent] = []
        self._release_hooks: List[Callable] = []
        self._client: Optional[Client] = None
        self._extract_range = None     # lazy bulk-segment extractor
        self._extract_multi = None     # lazy multi-slot segment extractor

        # ---- jitted step functions ---------------------------------------
        self._extract = self.layout.make_batched_extractor()
        load_static = ("with_load",) if self.api.reports_load else ()
        self._decode = jax.jit(self.api.decode,
                               static_argnames=("capacity",) + load_static)
        # pad-free dispatch (batch["mask"] + real-token capacity) is a
        # transformer-family extension, marked by the prefill_chunk entry
        self.prefill_masked = self.api.prefill_chunk is not None
        pre_static = ("max_seq", "capacity") if self.prefill_masked \
            else ("max_seq",)
        self._prefill = jax.jit(self.api.prefill,
                                static_argnames=pre_static + load_static)
        # device-resident decode loop (serving/decode_loop.py): jitted
        # counter-based sampling + multi-token lax.scan segments. Sampling
        # lives on device for EVERY engine — the host-RNG path is gone.
        self.decode_plane = DecodeLoopPlane(self)
        if ecfg.decode_segment_len > 1:
            assert getattr(self.api, "supports_decode_segments", False), (
                f"decode_segment_len={ecfg.decode_segment_len} requires a "
                f"model family with a segmentable decode step (the "
                f"transformer family); {cfg.name} does not support it")
        self.steps = 0

        # padded prefill is only sound for pure full-attention caches:
        # recurrent-state leaves or ring buffers must never see pad tokens
        # (a layout question, so each layout answers it for its own cache)
        self.prefill_paddable = self.layout.prefill_paddable(
            self.cache, ecfg.max_seq)

        # ---- chunked-prefill plane (serving/chunked.py) -------------------
        # chunked streams need slot == absolute position, i.e. the padded
        # (full-attention) cache family; others keep the whole-prompt path
        self.chunked: Optional[ChunkedPrefillPlane] = None
        if ecfg.chunk_token_budget > 0 and self.prefill_paddable and \
                self.api.prefill_chunk is not None:
            # chunked == whole-prompt bit-identity relies on a common
            # online-softmax KV block partition: both the cache extent and
            # the padded bucket lengths must be PREFILL_BLOCK_K-aligned,
            # or _pick_block silently degrades to mismatched block sizes
            from repro.models.attention import PREFILL_BLOCK_K
            assert ecfg.max_seq % PREFILL_BLOCK_K == 0 and \
                ecfg.prefill_bucket % PREFILL_BLOCK_K == 0, (
                    f"chunked prefill requires max_seq and prefill_bucket "
                    f"to be multiples of PREFILL_BLOCK_K="
                    f"{PREFILL_BLOCK_K} (got max_seq={ecfg.max_seq}, "
                    f"prefill_bucket={ecfg.prefill_bucket})")
            self._prefill_chunk = jax.jit(
                self.api.prefill_chunk,
                static_argnames=("capacity",) + load_static)
            self.chunked = ChunkedPrefillPlane(
                self, ecfg.chunk_token_budget, min_chunk=ecfg.chunk_min)
            self.gateway.prefill_load = self.chunked.outstanding_tokens
        self.gateway.prefill_token_cap = ecfg.prefill_token_cap

        # ---- prefix-cache plane (serving/prefixcache.py) ------------------
        # per-AW radix index over committed KV prefixes: finished slots are
        # adopted instead of cleared, and later prompts sharing a prefix
        # chunk-prefill only the uncached tail. Requires the chunked plane
        # (adoption IS a mid-prompt resume of the chunk stream).
        self.prefix_plane: Optional[PrefixCachePlane] = None
        if ecfg.prefix_cache_slots > 0:
            assert self.chunked is not None, (
                "prefix_cache_slots > 0 requires the chunked-prefill plane "
                "(chunk_token_budget > 0 on a full-attention cache family)")
            self.prefix_plane = PrefixCachePlane(
                self, ecfg.prefix_cache_slots, ecfg.prefix_cache_tokens,
                min_match=ecfg.prefix_min_match)
        assert self.prefix_plane is not None or not (
            ecfg.prefix_global_index or ecfg.prefix_migrate), (
            "prefix_global_index/prefix_migrate require the prefix-cache "
            "plane (prefix_cache_slots > 0)")
        assert ecfg.victim_policy in ("remaining_work", "youngest",
                                      "controller"), (
            f"unknown victim_policy {ecfg.victim_policy!r}")

        # ---- control plane (serving/controller.py) ------------------------
        # one decision pass per tick over signals the stack already emits,
        # actuating only through existing mechanisms — host-side only, so
        # controller on/off is bit-identical under identical decisions and
        # adds zero new jit traces by construction
        assert ecfg.controller in ("off", "on"), (
            f"unknown controller mode {ecfg.controller!r}")
        self.controller: Optional[ServingController] = None
        if ecfg.controller == "on":
            self.controller = ServingController(self)
        assert ecfg.victim_policy != "controller" or \
            self.controller is not None, (
            'victim_policy="controller" requires controller="on"')

        # ---- forensics plane (serving/flightrec.py) -----------------------
        # bounded-memory black box + health watchdogs, riding the bus as
        # its own consumer. Host-side bookkeeping only, like telemetry:
        # on/off is bit-identical and adds zero new jit traces.
        self.flightrec: Optional[flightrec.FlightRecorder] = None
        if ecfg.flight_recorder:
            self.flightrec = flightrec.FlightRecorder(self)
        self.gateway.flightrec = self.flightrec
        assert not ecfg.watchdogs or ecfg.flight_recorder, (
            "watchdogs=True requires flight_recorder=True (the watchdogs "
            "ride the recorder's bus cursor and trip its dump)")

    # ------------------------------------------------------------------
    # decode routing capacity (§5.2): the decode path may run at a tighter
    # capacity factor than prefill — fewer tokens per step means the
    # default (prefill-sized) factor over-provisions slot capacity
    # ------------------------------------------------------------------
    @property
    def decode_capacity(self) -> Optional[int]:
        cf = self.ecfg.capacity_factor_decode
        if not cf or not self.cfg.moe.enabled:
            return None
        return int(max(1, round(cf * self.cfg.moe.top_k *
                                self.ecfg.max_batch /
                                self.cfg.moe.num_experts)))

    def prefill_capacity(self, n_real_tokens: int) -> Optional[int]:
        """Expert capacity for a prefill/chunk call, computed from the
        REAL token count (pads are excluded from rank competition by the
        dispatch mask) and rounded up to a power of two — jit keys stay
        bounded, and a request's routing no longer depends on how much
        padding its batch happens to carry."""
        if not self.prefill_masked or not self.cfg.moe.enabled:
            return None
        cap = int(max(1, round(self.cfg.moe.capacity_factor *
                               self.cfg.moe.top_k * n_real_tokens /
                               self.cfg.moe.num_experts)))
        p = 1
        while p < cap:
            p *= 2
        return p

    # ------------------------------------------------------------------
    # sampling (the decode head): device-resident, serving/decode_loop.py.
    # The host shim below survives only for external callers.
    # ------------------------------------------------------------------
    def sample_token(self, row_logits: np.ndarray,
                     sampling: Optional[SamplingParams] = None, *,
                     seed: Optional[int] = None, pos: int = 0) -> int:
        """DEPRECATED host-side sampling shim. The serving stack samples on
        device (``decode_plane``); this remains for external callers that
        hold host logits. Top-k slices the k candidate rows *before* the
        softmax (float32 throughout — no full-vocab float64 partition), and
        the draw is counter-based (Philox keyed on (seed, pos)) instead of
        stateful, matching the device sampler's reproducibility contract
        though not its bitstream."""
        greedy = self.ecfg.greedy if sampling is None else sampling.greedy
        temperature = self.ecfg.temperature if sampling is None \
            else sampling.temperature
        top_k = self.ecfg.top_k if sampling is None else sampling.top_k
        if greedy:
            return int(np.argmax(row_logits))
        logits = np.asarray(row_logits, np.float32)
        v = logits.size
        if top_k and top_k < v:
            idx = np.argpartition(logits, v - top_k)[v - top_k:]
        else:
            idx = np.arange(v)
        sub = logits[idx] / np.float32(max(temperature, 1e-6))
        sub = sub - sub.max()
        p = np.exp(sub)
        p /= p.sum()
        s = self.ecfg.sample_seed if seed is None else seed
        rng = np.random.Generator(
            np.random.Philox(key=[s & 0xFFFFFFFFFFFFFFFF, max(pos, 0)]))
        return int(idx[rng.choice(idx.size, p=p)])

    # ------------------------------------------------------------------
    # admission (delegates to Gateway + ContinuousBatchScheduler)
    # ------------------------------------------------------------------
    def choose_aw(self) -> Optional[int]:
        return self.gateway.choose_aw()

    def make_request_state(self, q: QueuedRequest, slot: int
                           ) -> RequestState:
        st = RequestState(rid=q.rid, slot=slot, prompt=q.prompt,
                          max_new=q.max_new, t_enqueue=q.t_enqueue,
                          slo_class=q.slo_class, deadline=q.deadline,
                          completion_deadline=q.completion_deadline,
                          sampling=q.sampling, session=q.session,
                          prefix_hit=q.prefix_hit,
                          # a miss flagged while queued is not re-flagged
                          deadline_flagged=q.deadline_flagged,
                          completion_flagged=q.completion_flagged)
        # slot-indexed sampling arrays ride the slot assignment (recovery
        # re-binds through _install_recovery)
        self.decode_plane.bind(st)
        return st

    @property
    def client(self) -> Client:
        """The typed request-API front door (serving/api.py): submit
        ``RequestSpec``s, get ``RequestHandle``s with status/streaming/
        cancel. Lazily constructed; multiple explicit Clients over one
        engine are also fine."""
        if self._client is None:
            self._client = Client(self)
        return self._client

    def add_release_hook(self, fn: Callable):
        """Register fn(RequestState) to run when a request is released
        (done, cancelled, or torn down) — clients pin final states onto
        their handles through this."""
        self._release_hooks.append(fn)

    def submit(self, rid: str, prompt: np.ndarray, max_new: int,
               frames: Optional[np.ndarray] = None,
               now: float = 0.0) -> bool:
        """DEPRECATED positional shim over the typed request API: enqueue
        as a standard-class request and admit immediately; refuse (rather
        than queue) when no AW has capacity — the historical synchronous
        semantics, pinned by tests/test_request_api.py. New code should use
        ``engine.client.submit(RequestSpec(...))``, which queues instead of
        refusing and returns a RequestHandle."""
        warnings.warn(
            "InferenceEngine.submit(rid, prompt, max_new) is deprecated; "
            "use engine.client.submit(RequestSpec(...)) -> RequestHandle",
            DeprecationWarning, stacklevel=2)
        return self._submit_sync(rid, prompt, max_new, frames=frames,
                                 now=now)

    def _submit_sync(self, rid: str, prompt: np.ndarray, max_new: int,
                     frames: Optional[np.ndarray] = None,
                     now: float = 0.0, slo_class: str = STANDARD,
                     deadline: Optional[float] = None,
                     sampling: Optional[SamplingParams] = None,
                     session: Optional[str] = None) -> bool:
        """Synchronous admission (internal): enqueue and admit immediately;
        refuse (rather than queue) when no AW has capacity — the
        waiting-queue path is the serving loop's (run_serving drives the
        Gateway directly)."""
        self.gateway.enqueue(rid, prompt, max_new, now=now, frames=frames,
                             slo_class=slo_class, deadline=deadline,
                             sampling=sampling, session=session)
        admitted = self.scheduler.admit(now)
        if rid in admitted:
            return True
        self.gateway.drop(rid)
        if self.telemetry is not None:
            self.telemetry.on_drop(rid, now, "refused")
        return False

    # ------------------------------------------------------------------
    # decode step (delegates to the scheduler)
    # ------------------------------------------------------------------
    def active_requests(self) -> List[RequestState]:
        return [r for r in self.requests.values()
                if not r.done and not r.paused and not r.prefilling]

    def prefilling_requests(self) -> List[RequestState]:
        return [r for r in self.requests.values()
                if r.prefilling and not r.done and not r.paused]

    def step(self, now: Optional[float] = None) -> Dict[str, List[int]]:
        """One iteration: a budgeted slice of chunked prefill (when the
        plane is on) followed by one decode *segment* over all active slots
        (``decode_segment_len`` device steps per dispatch; 1 = classic
        per-step cadence). Returns {rid: new_tokens} — one entry per token
        the segment emitted for that request."""
        return self.scheduler.step(now)

    # ------------------------------------------------------------------
    # prefill accounting (virtual-clock work charging + metrics)
    # ------------------------------------------------------------------
    def prefill_tokens_done(self) -> int:
        """Total real prompt tokens prefilled so far, across the
        whole-prompt path and the chunked plane."""
        n = self.scheduler.stats.real_tokens
        if self.chunked is not None:
            n += self.chunked.stats.real_tokens
        return n

    def prefill_snapshot(self) -> dict:
        snap = self.scheduler.stats.snapshot()
        if self.chunked is not None:
            snap["chunked"] = self.chunked.stats.snapshot()
        return snap

    # ------------------------------------------------------------------
    # request lifecycle: preemption, cancellation, deadlines
    # (serving/api.py) — the recovery subsystem doubling as the
    # scheduling substrate: a preempted request is checkpointed out of
    # its slot and re-enters exactly like a crash-recovered one.
    # ------------------------------------------------------------------
    def _note_request_event(self, kind: str, rid: str, now: float,
                            detail: str = ""):
        ev = WorkerEvent(now, kind, rid, detail)
        self.request_log.append(ev)
        # publish-at-emission: the bus carries the same event for every
        # cursor-based consumer; the request_log stays as a legacy
        # destructive view for the orchestrator timeline
        self.bus.publish(ev)
        if self.telemetry is not None:
            self.telemetry.on_request_event(ev)

    def drain_request_events(self) -> List[WorkerEvent]:
        evs, self.request_log = self.request_log, []
        # placement-plane events (session_repinned) ride the same timeline
        evs = evs + self.gateway.drain_events()
        return evs

    @staticmethod
    def _remaining_work(r: RequestState) -> int:
        """Remaining-work estimate for victim selection: decode tokens
        still owed plus the prefill debt (un-prefilled prompt tokens) —
        a mid-prefill request has barely invested anything yet, so it is
        the cheapest to push aside."""
        debt = (len(r.prompt) - 1 - r.prefill_cursor) if r.prefilling else 0
        return (r.max_new - len(r.tokens)) + debt

    def _choose_victim(self, exclude: str = "", head=None,
                       now: float = 0.0) -> Optional[RequestState]:
        """Pick the preemption victim among preemptible-class requests
        resident on live AWs.

        ``victim_policy="remaining_work"`` (default): evict the request
        with the MOST work left (``max_new - emitted`` plus prefill debt)
        — it has invested the least and wastes the fewest finished
        tokens. ``victim_policy="youngest"``: the pre-PR-5 behavior — the
        latest arrival by ``t_enqueue`` (stable across restores, unlike
        ``t_admit``, which resets on every re-admission and would pin the
        same just-restored victim in an evict/restore ping-pong). Both
        policies prefer, among equals, the candidate evicted the fewest
        times (repeated preemptions rotate through a wave instead of
        starving one rid), with a final rid tie-break for determinism.

        ``victim_policy="controller"`` delegates to the control plane's
        deadline- and prefix-aware policy: batch work is evicted only when
        the blocked head's deadline is actually at risk, and the victim
        score prices in its exclusive paged-KV / resident-prefix value
        (an eviction tears that down and the restore path must rebuild
        it). The candidate filter is shared, so interactive work can
        never be a victim under ANY policy."""
        cands = [r for r in self.requests.values()
                 if r.slo_class in PREEMPTIBLE_CLASSES and not r.done
                 and not r.paused and not r.cancelled
                 and not r.queued_for_recovery and r.rid != exclude
                 and r._aw >= 0 and self.aws[r._aw].alive]
        if not cands:
            return None
        if self.ecfg.victim_policy == "controller":
            return self.controller.choose_victim(cands, head=head, now=now)
        if self.ecfg.victim_policy == "youngest":
            return max(cands, key=lambda r: (r.t_enqueue, -r.preemptions,
                                             r.rid))
        return max(cands, key=lambda r: (self._remaining_work(r),
                                         -r.preemptions, r.rid))

    def _preempt_for(self, head: QueuedRequest, now: float) -> bool:
        """Gateway preemptor hook: a blocked interactive head asks for a
        slot; evict a batch victim if one exists."""
        victim = self._choose_victim(exclude=head.rid, head=head, now=now)
        if victim is None:
            return False
        return self.preempt_request(victim.rid, now=now)

    def preempt_request(self, rid: str, now: float = 0.0) -> bool:
        """Planned eviction (preempt-and-requeue): commit the victim's
        resident KV to the checkpoint store through the bulk-segment path,
        release its slot, and requeue it as a recovery entry at the front
        of its class queue. On re-admission it restores the committed
        prefix and resumes from the cursor — decode requests rewind zero
        tokens (the watermark is flushed first), chunked-prefill requests
        resume mid-stream. Preemption is failure you chose: it rides
        §6.1/§6.2 unchanged, needs no health-mask flip, and triggers no
        new jit traces."""
        r = self.requests.get(rid)
        if r is None or r.done or r.paused or r.cancelled or \
                r.queued_for_recovery or r._aw < 0:
            return False
        aw = self.aws[r._aw]
        if not aw.alive:
            return False
        committed = self._commit_resident_kv(r)
        if self.chunked is not None:
            self.chunked.drop(rid)
        aw.prefills.pop(rid, None)
        if self.prefix_plane is not None:
            # an adopted prefix entry cannot outlive the eviction: the
            # slot is about to be cleared (the victim's own log carries
            # everything it needs to resume)
            self.prefix_plane.forget_slot(r._aw, r.slot)
        self._kv_clear_slot(r.slot)
        aw.slots.release(r.slot)
        r.paused = True
        r.queued_for_recovery = True
        r.preemptions += 1
        self.gateway.requeue_recovery([QueuedRequest(
            rid, r.prompt, r.max_new, frames=None, t_enqueue=now,
            slo_class=r.slo_class, deadline=r.deadline,
            completion_deadline=r.completion_deadline,
            completion_flagged=r.completion_flagged,
            sampling=r.sampling, session=r.session)])
        self.gateway.stats.preemptions += 1
        self.gateway.stats.bump(r.slo_class, "preempted")
        self._note_request_event(
            "preempted", rid, now,
            f"slot freed on aw{aw.aw_id}, resume@{committed + 1}")
        if self.telemetry is not None:
            self.telemetry.on_preempt(rid, now)
        return True

    def _commit_resident_kv(self, r: RequestState) -> int:
        """Bring the checkpoint store's commit watermark up to the
        victim's full resident state. Planned eviction *delivers* pending
        WRs (flush) — this is not a crash — and any resident KV beyond the
        watermark (e.g. the whole prefix on a checkpoint=False engine)
        streams out through the bulk-segment path
        (``KVCheckpointer.checkpoint_range``). Returns the committed token
        index the request will resume from."""
        ck = self.aws[r._aw].checkpointer
        n = len(r.prompt)
        if self.ecfg.checkpoint:
            ck.flush()
        else:
            # un-protected request: first eviction registers it with the
            # store (preemption turns checkpointing on for this rid alone)
            ck.register(r.rid, prompt_len=n)
        committed = self.store.committed_token(r.rid)
        last = (r.prefill_cursor if r.prefilling else r.pos) - 1
        if committed < last:
            self._bulk_checkpoint(r, committed + 1, last)
            ck.flush()
            committed = self.store.committed_token(r.rid)
        assert committed == last, (
            f"preempt {r.rid}: watermark {committed} != resident {last}")
        return committed

    def _bulk_checkpoint(self, r: RequestState, start: int, last: int):
        """Stream token segments [start, last] of the request's slot to
        the store via the bulk range extractor (chunk-shaped static counts
        keep jit keys O(log max_seq))."""
        if self._extract_range is None:
            # share the chunked plane's jitted extractor when it exists —
            # an identical second extractor would just double the traces
            self._extract_range = self.chunked._extract_range \
                if self.chunked is not None \
                else self.layout.make_slot_range_extractor()
        ck = self.aws[r._aw].checkpointer
        if self.chunked is not None:
            # the shared extractor was traced with the plane's shape set —
            # use the same cap so bulk segments never mint a new jit key
            max_shape = self.chunked.max_shape
        else:
            max_shape = 1
            while max_shape * 2 <= self.ecfg.max_seq:
                max_shape *= 2
        t = start
        while t <= last:
            count = min(last - t + 1, max_shape)
            shape = 1
            while shape < count:
                shape *= 2
            shape = min(shape, max_shape)
            base = max(0, min(t, self.ecfg.max_seq - shape))
            seg_stack = [np.asarray(a)[t - base:t - base + count]
                         for a in self._extract_range(
                             self.cache, r.slot, base, count=shape)]
            self.note_syncs()
            self._ck_range(ck, r.rid, t, seg_stack,
                           [self._ck_token_value(r, i)
                            for i in range(t, t + count)])
            t += count

    @staticmethod
    def _ck_token_value(r: RequestState, t: int) -> int:
        # the store hands back position t's *next decode input*: a prompt
        # token while t+1 is still in the prompt, else the generated token
        # whose sampling consumed position t
        n = len(r.prompt)
        if t + 1 < n:
            return int(r.prompt[t + 1])
        k = t - n + 1
        return int(r.tokens[k]) if 0 <= k < len(r.tokens) else -1

    def _bulk_checkpoint_group(self, items):
        """Segment-boundary checkpointing for MANY requests in one device
        gather (the per-segment analogue of the per-token batched
        extract): ``items`` is [(request, start, n_tokens)]. Requests are
        grouped by pow2 segment shape and rows pow2-padded, so one jitted
        multi-slot extract serves the whole decode segment; segments then
        fan out to each request's AW checkpointer host-side."""
        if self._extract_multi is None:
            self._extract_multi = self.layout.make_multi_slot_range_extractor()
        if self.chunked is not None:
            max_shape = self.chunked.max_shape
        else:
            max_shape = 1
            while max_shape * 2 <= self.ecfg.max_seq:
                max_shape *= 2
        groups: Dict[int, list] = {}
        for r, start, cnt in items:
            if cnt <= 0:
                continue
            if cnt > max_shape:    # oversized: the scalar path chunks it
                self._bulk_checkpoint(r, start, start + cnt - 1)
                continue
            shape = 1
            while shape < cnt:
                shape *= 2
            groups.setdefault(shape, []).append((r, start, cnt))
        for shape, ent in sorted(groups.items()):
            rows = 1
            while rows < len(ent):
                rows *= 2
            slots = np.zeros((rows,), np.int32)
            bases = np.zeros((rows,), np.int32)
            for i, (r, start, _) in enumerate(ent):
                slots[i] = r.slot
                bases[i] = max(0, min(start, self.ecfg.max_seq - shape))
            with span(self.telemetry, "engine", "checkpoint.device"):
                stacked = [np.asarray(a) for a in self._extract_multi(
                    self.cache, jnp.asarray(slots), jnp.asarray(bases),
                    count=shape)]
            self.note_syncs()
            for i, (r, start, cnt) in enumerate(ent):
                off = start - bases[i]
                seg_stack = [a[i][off:off + cnt] for a in stacked]
                self._ck_range(self.aws[r._aw].checkpointer,
                               r.rid, start, seg_stack,
                               [self._ck_token_value(r, t)
                                for t in range(start, start + cnt)])

    def _ck_range(self, ck, rid: str, start: int, seg_stack, token_values):
        """Bulk-range checkpointing, block-granular on a paged engine: WR
        batches split at physical page boundaries (checkpoint_blocks), so
        a page's worth of KV commits or dies together. The store's
        segments stay token-granular and layout-independent either way —
        a paged AW's checkpoints restore onto a contiguous engine and
        vice versa."""
        self.note_checkpoint(len(token_values), _seg_nbytes(seg_stack))
        if self.pages is not None:
            ck.checkpoint_blocks(rid, start, seg_stack, token_values,
                                 self.pages.page_tokens)
        else:
            ck.checkpoint_range(rid, start, seg_stack, token_values)

    # ------------------------------------------------------------------
    # paged-KV facades: every clear / scrub / extend of a slot's resident
    # KV routes through here so contiguous and paged engines share call
    # sites (chunked planner, batching, recovery, preemption, release).
    # On a contiguous engine each facade is a pass-through to the layout;
    # on a paged engine it also runs the host allocator (refcounts, per-AW
    # free lists) and keeps the device block table in sync. All device
    # work goes through jitted-once helpers — zero new traces at runtime.
    # ------------------------------------------------------------------
    def _kv_sync_bt(self):
        """Upload the host block-table mirror when it drifted (a [B,nblk]
        int32 copy — the only per-allocation device traffic)."""
        if self.pages is not None and self.pages.dirty:
            self.cache = self.layout.set_block_table(self.cache,
                                                     self.pages.bt)
            self.pages.dirty = False

    def _kv_free_pages(self, pids):
        """Scrub freed pages' positions on device before they can
        recycle: a stale ``pos >= 0`` entry would leak the old mapper's
        KV into the next mapper's attention."""
        if pids:
            self.cache = self.layout.scrub_pages(self.cache, pids)

    def _kv_reclaim(self, aw: int):
        """Page pressure: evict cached prefixes on ``aw`` (tail pages
        first, exclusive pages only ever free — a page with refcount > 1
        survives its holder) until a page frees or nothing is evictable."""
        pc = self.aws[aw].prefix_cache
        evict = getattr(pc, "evict_pages", None)
        while self.pages.free_pages(aw) == 0 and evict is not None:
            freed = evict()
            if not freed:
                break
            self._kv_free_pages(freed)

    def _kv_ensure(self, slot: int, upto: int):
        """Pre-allocate pages so positions [0, upto) of ``slot`` are
        mapped before a prefill chunk / decode segment writes them.
        No-op on a contiguous engine (the slot owns its whole extent)."""
        if self.pages is None or upto <= 0:
            return
        pool = self.pages
        need = -(-min(upto, self.ecfg.max_seq) // pool.page_tokens)
        aw = pool.aw_of_slot(slot)
        for blk in range(need):
            if pool.bt[slot, blk] > 0:
                continue
            pid = pool.alloc(aw)
            if pid < 0:
                self._kv_reclaim(aw)
                pid = pool.alloc(aw)
            if pid < 0:
                raise RuntimeError(
                    f"AW{aw} out of KV pages: slot {slot} needs block "
                    f"{blk} ({need} total) and nothing is evictable")
            pool.map_block(slot, blk, pid)
        self._kv_sync_bt()

    def _kv_clear_slot(self, slot: int):
        """Release a slot's resident KV. Contiguous: scrub the slot's
        rows. Paged: unmap the block-table row and decref its pages —
        pages shared with a cached prefix entry (or another adopter)
        survive; exclusive pages scrub and return to the AW's free
        list."""
        if self.pages is None:
            self.cache = self.layout.clear_slot(self.cache, slot)
            return
        self._kv_free_pages(self.pages.release_slot(slot))
        self.cache = self.layout.clear_slot(self.cache, slot)
        self._kv_sync_bt()

    def _kv_scrub_slot(self, slot: int, valid_len: int):
        """Mask positions >= valid_len in the slot (prefix adoption keeps
        [0, valid_len) live). Paged writes to shared pages are value-
        identical by construction — a fully-shared page only holds
        positions below the hit."""
        self.cache = self.layout.scrub_slot(self.cache, slot, valid_len)

    def _kv_adopt(self, slot: int, pages, hit: int) -> int:
        """Map a cached prefix entry's pages into ``slot`` (copy-on-
        extend): pages fully below the hit are SHARED — the same physical
        page, refcount bumped, zero KV copied — and the boundary page
        (the one the adopter will extend past the hit) is duplicated into
        a private page. Returns the usable hit length: when no page is
        free for the boundary copy it degrades to the last full-page
        boundary rather than failing the adoption."""
        pool = self.pages
        pt = pool.page_tokens
        full = min(hit // pt, len(pages))
        aw = pool.aw_of_slot(slot)
        for b in range(full):
            pool.incref(pages[b])
            pool.map_block(slot, b, pages[b])
        rem = hit - full * pt
        if rem > 0 and full < len(pages):
            # pin the boundary source first: reclaim may trim the very
            # entry being adopted, and an unpinned boundary page could be
            # freed (and scrubbed) before the copy reads it
            src = int(pages[full])
            pool.incref(src)
            pid = pool.alloc(aw)
            if pid < 0:
                self._kv_reclaim(aw)
                pid = pool.alloc(aw)
            if pid < 0:
                hit = full * pt          # degrade: share whole pages only
            else:
                self.cache = self.layout.copy_page(self.cache, src, pid)
                pool.map_block(slot, full, pid)
            if pool.decref(src):
                self._kv_free_pages([src])
        elif rem > 0:
            hit = full * pt
        self._kv_sync_bt()
        return hit

    def _kv_snapshot(self, slot: int, n: int):
        """Pin the pages covering positions [0, n) of ``slot`` (one
        reference each) — the backing of a new prefix-cache entry. The
        entry's references keep the pages alive after the slot itself
        releases."""
        pool = self.pages
        blocks = -(-n // pool.page_tokens)
        pids = pool.slot_pages(slot, upto_blocks=blocks)
        for pid in pids:
            pool.incref(pid)
        return pids

    def cancel_request(self, rid: str, now: float = 0.0) -> bool:
        """Cancel a request anywhere in its lifecycle. Queued: the entry
        leaves its class queue. In flight: full teardown — the owning AW's
        slot is released, its pending checkpoint WRs and prefill cursor
        dropped, the chunk stream closed, and the store log freed.
        Preempted/paused: the recovery entry is dropped too. Other
        requests are untouched."""
        r = self.requests.get(rid)
        if r is None:
            entry = self.gateway.drop(rid)
            if entry is None:
                return False
            self.gateway.stats.bump(entry.slo_class, "cancelled")
            self._note_request_event("cancelled", rid, now, "while queued")
            if self.telemetry is not None:
                self.telemetry.on_drop(rid, now, "cancelled")
            return True
        if r.done:
            return False
        r.cancelled = True
        r.done = True
        self.gateway.stats.bump(r.slo_class, "cancelled")
        self._note_request_event("cancelled", rid, now, r.state)
        if self.telemetry is not None:
            self.telemetry.on_cancel(rid, now, "in_flight")
        self.release_request(rid)
        return True

    def _deadline_pass(self, now: float, *, completion: bool):
        """One flag-once sweep for one deadline kind, over both the
        Gateway queues and the resident requests. The kind differs only
        in which field/flag/counter it touches and in its met-SLO rule:
        first-token misses are excused when the first token landed in
        time (a crash-recovery entry of a request that already met its
        SLO is not a fresh miss), completion misses when the request is
        done."""
        attr = "completion_flagged" if completion else "deadline_flagged"
        counter = "completion_deadline_missed" if completion \
            else "deadline_missed"
        tag = "completion, " if completion else ""

        def deadline_of(x):
            return x.completion_deadline if completion else x.deadline

        for cls, q in self.gateway.queues.items():
            for e in q:
                dl = deadline_of(e)
                if dl is None or getattr(e, attr) or now <= dl:
                    continue
                setattr(e, attr, True)
                r = self.requests.get(e.rid)
                if r is not None:
                    if getattr(r, attr):
                        continue
                    if not completion and 0 <= r.t_first_token <= dl:
                        continue
                    setattr(r, attr, True)
                self.gateway.stats.bump(cls, counter)
                self._note_request_event("deadline_missed", e.rid, now,
                                         f"{tag}queued, deadline={dl:g}")
        for r in self.requests.values():
            dl = deadline_of(r)
            if dl is None or getattr(r, attr):
                continue
            if not completion and r.t_first_token >= 0:
                # admitted-late case: the first token itself arrived past
                # the deadline (possibly in the same tick as admission)
                if r.t_first_token <= dl:
                    continue
            elif r.done or now <= dl:
                continue
            setattr(r, attr, True)
            self.gateway.stats.bump(r.slo_class, counter)
            self._note_request_event("deadline_missed", r.rid, now,
                                     f"{tag}{r.state}, deadline={dl:g}")

    def check_deadlines(self, now: float):
        """Emit ``deadline_missed`` once per request whose first-token
        deadline passed — whether it is still queued at the Gateway or
        resident without a first token — and once per request whose
        **completion deadline** passed before its last token (counted
        separately as ``completion_deadline_missed``). The request is NOT
        dropped either way: deadlines are SLO signals (per-class counters
        in GatewayStats), not admission filters."""
        self._deadline_pass(now, completion=False)
        self._deadline_pass(now, completion=True)

    # ------------------------------------------------------------------
    # failure injection & recovery (delegates to the worker objects)
    # ------------------------------------------------------------------
    @property
    def failed_aws(self) -> set:
        return {w.aw_id for w in self.aws if not w.alive}

    @property
    def failed_ews(self) -> set:
        return {w.ew_id for w in self.ews if w.member and not w.alive}

    @property
    def live_ews(self) -> set:
        return {w.ew_id for w in self.ews if w.member and w.alive}

    @property
    def checkpointers(self) -> dict:
        return {w.aw_id: w.checkpointer for w in self.aws}

    def fail_ew(self, ew: int):
        self.route_state = self.ews[ew].fail(self.route_state)

    def fail_aw(self, aw: int):
        """AW crash: its slots (and un-checkpointed state) are gone; its
        requests pause until re-admitted through the Gateway. Requests
        with no checkpoint record (checkpoint=False) cannot be restored:
        they keep decoding against the dead worker's slot — the simulated
        data loss of a system without Tarragon's store — instead of being
        stranded in a paused state forever. Requests caught mid-prefill are
        preempted the same way: their chunk stream stops and recovery will
        resume it from the committed cursor."""
        if self.prefix_plane is not None:
            # snapshot the dying AW's cached prefixes before fail() clears
            # them: checkpoint-backed entries become restorable orphans
            self.prefix_plane.note_aw_failed(aw)
        if self.pages is not None:
            # the AW's physical pages die with it: drop the cache entries'
            # references first (orphan metadata is already snapshotted —
            # restoration replays from the store into fresh pages), then
            # unmap the partition's slots. Slots of UNRECOVERABLE requests
            # (no store record) keep their pages: those requests keep
            # decoding against the dead worker's state, mirroring the
            # contiguous engine's simulated-data-loss behaviour below.
            # Freed pages scrub so the clean-page invariant holds
            # unconditionally at re-provision.
            rec = set(self.store.active_requests_on(aw))
            keep = {r.slot for r in self.requests.values()
                    if r._aw == aw and not r.done and r.rid not in rec}
            freed = []
            pc = self.aws[aw].prefix_cache
            if pc is not None and hasattr(pc, "release_all_pages"):
                freed += pc.release_all_pages()
            per = self.slots.per_aw
            for s in range(aw * per, (aw + 1) * per):
                if s not in keep:
                    freed += self.pages.release_slot(s)
            self._kv_free_pages(freed)
            self._kv_sync_bt()
        self.route_state = self.aws[aw].fail(self.route_state)
        recoverable = set(self.store.active_requests_on(aw))
        if self.chunked is not None and self.ecfg.checkpoint:
            self.chunked.drop_aw(aw)
        for r in self.requests.values():
            if r._aw == aw and not r.done and r.rid in recoverable:
                r.paused = True

    def recover_aw_requests(self, now: float = 0.0) -> List[str]:
        """Per-request restoration (§6.2): requeue every affected request
        through the Gateway (front of the FIFO — they are the oldest work)
        and admit as many as current capacity allows; the rest stay queued
        and retry on subsequent ticks instead of being dropped. Returns the
        rids restored *now*."""
        entries = []
        for aw in sorted(self.failed_aws):
            for rid in self.store.active_requests_on(aw):
                r = self.requests.get(rid)
                if r is None or r.done or r.queued_for_recovery:
                    continue
                r.queued_for_recovery = True
                if self.telemetry is not None:
                    self.telemetry.on_failover(rid, now)
                # the recovery waiting spell starts now, not at arrival;
                # class/deadline/sampling survive the crash with the state
                entries.append(QueuedRequest(
                    rid, r.prompt, r.max_new, t_enqueue=now,
                    slo_class=r.slo_class, deadline=r.deadline,
                    completion_deadline=r.completion_deadline,
                    completion_flagged=r.completion_flagged,
                    sampling=r.sampling, session=r.session))
        self.gateway.requeue_recovery(entries)
        admitted = set(self.scheduler.admit(now))
        if self.prefix_plane is not None:
            # live requests took their slots first; now carry the dead
            # AWs' cached session prefixes over to healthy AWs (§6.2
            # applied to cache state) so future turns still hit
            self.prefix_plane.restore_orphans(now)
        return [q.rid for q in entries if q.rid in admitted]

    def provision_aw(self, aw: int):
        in_use = {r.slot for r in self.active_requests()}
        self.route_state = self.aws[aw].provision(self.route_state, in_use)

    def provision_ew(self, ew: int, repoint_protect: Optional[int] = None,
                     now: float = 0.0):
        self.route_state = self.ews[ew].provision(self.route_state)
        if self.placement_mgr is not None and \
                ew not in self.placement_mgr.members:
            self.placement_mgr.members = sorted(
                self.placement_mgr.members + [ew])
        if repoint_protect is not None:
            self.repoint_shadows(repoint_protect, now=now)

    def repoint_shadows(self, protect_ew: int, now: float = 0.0):
        """Background re-pointing of replica slots to protect ``protect_ew``
        (host-side weight push, off the failover critical path). With a
        placement manager this is a versioned plan install; the bank is
        gathered through ``slot_expert``, so no parameter surgery either
        way."""
        if self.api.placement is None or \
                self.api.placement.num_shadow_slots == 0:
            return
        if self.placement_mgr is not None:
            self.install_plan(
                self.placement_mgr.plan_reprotect(
                    protect_ew, dead_ews=tuple(self.failed_ews)), now=now)
        else:
            self.route_state = selfheal.repoint_shadows(
                self.route_state, self.api.placement, protect_ew)

    # ------------------------------------------------------------------
    # elastic expert plane (core/placement.py): versioned plan installs,
    # EW scale-out/scale-in, shadow promotion, load-aware rebalancing.
    # Every transition below is a pure RouteState array update — the jitted
    # decode/prefill steps never re-trace across placement generations.
    # ------------------------------------------------------------------
    def _plan_arrays(self, plan: PlacementPlan) -> dict:
        return dict(
            candidates=jnp.asarray(plan.candidates(), jnp.int32),
            slot_expert=jnp.asarray(plan.slot_expert, jnp.int32),
            slot_owner=jnp.asarray(plan.slot_owner, jnp.int32),
            split_slot=jnp.asarray(plan.split_slot, jnp.int32))

    def install_plan(self, plan: PlacementPlan, now: float = 0.0,
                     detail: str = ""):
        """Activate a placement generation (post-T_push: the orchestrator
        has already charged the weight-push time to the virtual clock)."""
        self.route_state = self.route_state._replace(
            **self._plan_arrays(plan))
        ev = WorkerEvent(now, "placement_changed", f"gen{plan.generation}",
                         detail or plan.reason)
        self.plan_log.append(ev)
        self.bus.publish(ev)
        if self.telemetry is not None:
            self.telemetry.registry.inc("placement.plans_installed")

    def drain_plan_events(self) -> List[WorkerEvent]:
        evs, self.plan_log = self.plan_log, []
        return evs

    @property
    def placement_generation(self) -> int:
        return self.placement_mgr.plan.generation \
            if self.placement_mgr is not None else 0

    def note_dispatch_load(self, slot_load):
        """Drain a device-side per-slot dispatch counter into the placement
        manager's EMA (the telemetry behind load-aware decisions)."""
        if self.placement_mgr is not None:
            if isinstance(slot_load, jax.Array):
                self.note_syncs()
            self.placement_mgr.record_slot_load(np.asarray(slot_load))

    def note_syncs(self, n: int = 1):
        """Count ``n`` device->host drains (``step.device_syncs``)."""
        if self.telemetry is not None:
            self.telemetry.registry.inc("step.device_syncs", n)

    def note_checkpoint(self, segments: int, nbytes: int):
        """Count KV segments captured for checkpointing, and their bytes
        (``checkpoint.segments``, ``checkpoint.bytes``)."""
        if self.telemetry is not None:
            self.telemetry.registry.inc("checkpoint.segments", segments)
            self.telemetry.registry.inc("checkpoint.bytes", nbytes)

    def choose_protect_ew(self, exclude=()) -> Optional[int]:
        if self.placement_mgr is None:
            return None
        return self.placement_mgr.choose_protect_ew(tuple(exclude))

    def add_ew(self, now: float = 0.0) -> int:
        """Scale-out: admit a spare EW into the pool (layer-aligned join —
        the plan installs between steps, after the orchestrator charged
        T_w + T_push)."""
        assert self.placement_mgr is not None, "elastic plane requires MoE"
        new_ew, plan = self.placement_mgr.plan_scale_out()
        self.route_state = self.ews[new_ew].provision(self.route_state)
        self.install_plan(plan, now=now)
        return new_ew

    def drain_ew(self, ew: int, now: float = 0.0):
        """Graceful scale-in: the EW's resident experts have been migrated
        (T_push already charged); it leaves the pool as a spare."""
        assert self.placement_mgr is not None
        plan = self.placement_mgr.plan_scale_in(ew)
        self.install_plan(plan, now=now)
        self.route_state = self.ews[ew].retire(self.route_state)

    def promote_shadows(self, dead_ew: int, now: float = 0.0):
        """Permanent shadow promotion: instead of waiting for revival, the
        dead EW's replicas become primaries and the pool shrinks. Instant
        and push-free — promotion is an ERT flip, the weights are already
        resident (§5.3 taken to its logical end)."""
        assert self.placement_mgr is not None
        plan = self.placement_mgr.promote_shadows(dead_ew)
        self.ews[dead_ew].member = False
        self.install_plan(plan, now=now)

    def rebalance(self, now: float = 0.0) -> Optional[PlacementPlan]:
        """Load-aware re-packing of experts over the currently *healthy*
        pool members (a failed EW awaiting revival must not be handed
        primaries it cannot serve)."""
        if self.placement_mgr is None:
            return None
        plan = self.placement_mgr.plan_rebalance(
            live=tuple(self.live_ews))
        self.install_plan(plan, now=now)
        return plan

    def release_request(self, rid: str):
        """Full teardown of one request's footprint across the stack: the
        chunk stream, any stale recovery entry, the owning AW's slot +
        prefill cursor + pending checkpoint WRs, and the store log. Safe
        for done, cancelled, preempted, and crash-paused requests alike
        (the slot is only released when this request still holds it).

        With the prefix-cache plane on, a *completed* request's slot is
        offered to the owning AW's cache instead of being cleared: the
        cache adopts the slot AND the store log (the entry's restoration
        backing), so neither is freed here on a successful offer."""
        r = self.requests.pop(rid, None)
        if r is None:
            return
        # deadline backstop: a request whose first token landed late and
        # which finished before the next check_deadlines tick still counts
        if r.deadline is not None and not r.deadline_flagged and \
                r.t_first_token > r.deadline:
            r.deadline_flagged = True
            self.gateway.stats.bump(r.slo_class, "deadline_missed")
            self._note_request_event("deadline_missed", rid,
                                     r.t_first_token,
                                     f"first token at {r.t_first_token:g} "
                                     f"> deadline {r.deadline:g}")
        # completion-deadline backstop: finished late, released before the
        # next check_deadlines tick
        if r.completion_deadline is not None and not r.completion_flagged \
                and r.t_done > r.completion_deadline:
            r.completion_flagged = True
            self.gateway.stats.bump(r.slo_class, "completion_deadline_missed")
            self._note_request_event(
                "deadline_missed", rid, r.t_done,
                f"completion at {r.t_done:g} > deadline "
                f"{r.completion_deadline:g}")
        if self.chunked is not None:
            self.chunked.drop(rid)
        if r.queued_for_recovery:
            # cancel the pending re-admission: a stale recovery entry must
            # not reach the scheduler after the request is gone
            self.gateway.drop(rid)
        cached = False
        if r._aw >= 0 and self.aws[r._aw].alive:
            aw = self.aws[r._aw]
            if not r.paused and self.prefix_plane is not None and \
                    r.done and not r.cancelled:
                # commit the resident tail, then offer the slot (with its
                # KV and store log) to the AW's prefix cache
                aw.checkpointer.flush()
                cached = self.prefix_plane.offer(r)
            # pending WRs and the prefill cursor die with the request, not
            # with the worker (they reference a log about to be released)
            aw.drop_request(rid)
            if not r.paused and (not cached or self.pages is not None):
                # paged: the slot ALWAYS releases, cached or not — a
                # successful offer pinned its own page references, so the
                # shared pages outlive the slot while exclusive pages
                # free. Contiguous: a cached slot is retained by the
                # entry (slot-level sharing) and must not be cleared.
                if self.prefix_plane is not None and not cached:
                    # e.g. a cancelled adopter: its slot's live cache
                    # entry must not survive the clear below
                    self.prefix_plane.forget_slot(r._aw, r.slot)
                self._kv_clear_slot(r.slot)
                aw.slots.release(r.slot)
        # always safe: a cached entry's backing log was renamed to its
        # reserved ~prefix key (release of the original rid is then a
        # no-op), and on checkpoint=False engines a cached slot may still
        # own a stale log a preemption created under this rid — leaving
        # it would corrupt a later submission reusing the rid
        self.store.release(rid)
        if self.telemetry is not None:
            self.telemetry.on_release(r)
        if self.flightrec is not None:
            self.flightrec.on_release(r)
        for hook in self._release_hooks:
            hook(r)

    # ------------------------------------------------------------------
    def generate(self, rid: str, prompt: np.ndarray, max_new: int
                 ) -> List[int]:
        """Convenience: run one request to completion."""
        assert self._submit_sync(rid, prompt, max_new)
        r = self.requests[rid]
        while not r.done:
            self.step()
        return r.tokens
