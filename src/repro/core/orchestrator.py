"""Centralized orchestrator (paper Fig. 5): liveness monitoring, ERT/health
updates on failures, per-request restoration triggering, background worker
provisioning, and — on top of the versioned placement plane
(core/placement.py) — EW pool elasticity: scale-out/scale-in with the
weight-push time ``T_push`` modeled on the virtual clock, permanent shadow
promotion as an alternative to revival, and load-aware rebalancing driven
by the placement manager's dispatch-load EMAs.

Failure detection model (§5 + App. E): implicit heartbeats are the per-step
data-plane activity; a silent worker gets explicit probes every
``detect_interval``; after ``retries`` consecutive timeouts the worker is
declared fail-stop and self-healing fires.

EW failure policies:
  * ``revive``  (default) — classic §5.4: shadows absorb traffic, a
    replacement worker is provisioned in the background (T_w) and the
    shadow slots are re-pointed to protect the placement manager's choice
    of most-load-critical EW (no more hardcoded neighbor).
  * ``promote`` — elastic: the dead EW's shadows are promoted to primaries
    *permanently* (instant ERT flip, zero weight movement) and the pool
    shrinks; a re-protection plan (fresh replicas for the now most-critical
    EW) lands after T_push. Recovery becomes a routing update, not a
    revival event.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

from repro.core.costmodel import TarragonProfile
from repro.serving.telemetry import span


@dataclass
class WorkerEvent:
    t: float
    kind: str       # fail_aw|fail_ew|detected|healed|provisioned|
    #                 placement_changed|scale_out_started|scaled_out|
    #                 drain_started|scaled_in|rebalance_started|rebalanced|
    #                 preempted|cancelled|deadline_missed (request plane)
    worker: str
    detail: str = ""


@dataclass
class _PendingFailure:
    kind: str
    worker_id: int
    t_fail: float
    detected: bool = False


@dataclass
class _PendingProvision:
    kind: str       # "aw" | "ew" | "reprotect"
    worker_id: int
    t_ready: float


@dataclass
class _PendingScale:
    kind: str       # "add_ew" | "drain_ew" | "rebalance"
    worker_id: int  # -1 for add/rebalance
    t_ready: float


class Orchestrator:
    def __init__(self, engine, profile: Optional[TarragonProfile] = None,
                 worker_init_time: float = 18.5,
                 weight_push_time: float = 1.0,
                 ew_policy: str = "revive",
                 auto_rebalance: bool = False,
                 rebalance_cooldown: float = 2.0):
        assert ew_policy in ("revive", "promote")
        self.engine = engine
        self.profile = profile or TarragonProfile()
        self.T_w = worker_init_time
        self.T_push = weight_push_time
        self.ew_policy = ew_policy
        self.auto_rebalance = auto_rebalance
        self.rebalance_cooldown = rebalance_cooldown
        self._last_rebalance = -1e30
        self.events: List[WorkerEvent] = []
        self._failures: List[_PendingFailure] = []
        self._provisions: List[_PendingProvision] = []
        self._scales: List[_PendingScale] = []
        # telemetry plane (serving/telemetry.py): control-plane events
        # publish to the engine's bus at emission, so cursor-based
        # consumers see them without waiting for (or racing) this
        # orchestrator's own audit log
        self.bus = getattr(engine, "bus", None)
        # control plane (serving/controller.py): the controller decides,
        # this orchestrator actuates — attach so scale/rebalance requests
        # land on the same virtual clock as operator-driven ones
        ctl = getattr(engine, "controller", None)
        if ctl is not None:
            ctl.attach_orchestrator(self)
        # forensics plane (serving/flightrec.py): pin this orchestrator's
        # timing/policy parameters so a postmortem bundle can rebuild an
        # identically-clocked one for replay
        fr = getattr(engine, "flightrec", None)
        if fr is not None:
            fr.note_orchestrator(self)

    def _span(self, name: str, **args):
        """A host span on the engine's telemetry plane, track
        ``recovery``."""
        return span(getattr(self.engine, "telemetry", None), "recovery",
                    name, **args)

    def _emit(self, ev: WorkerEvent):
        self.events.append(ev)
        if self.bus is not None:
            self.bus.publish(ev)
        return ev

    # -- failure injection (the SIGINT of §7.2) -----------------------------
    def inject_failure(self, kind: str, worker_id: int, now: float):
        assert kind in ("aw", "ew")
        self._failures.append(_PendingFailure(kind, worker_id, now))
        self._emit(WorkerEvent(now, f"fail_{kind}", f"{kind}{worker_id}"))

    def detection_latency(self) -> float:
        return self.profile.detect * self.profile.detect_retries

    # -- elasticity requests (complete after T_w / T_push on the clock) -----
    def request_scale_out(self, now: float):
        """Grow the EW pool by one: worker init (T_w) + expert weight push
        (T_push) happen in the background; the layer-aligned join (§5.4)
        installs the new plan between steps once both complete. Validated
        at request time — a bad request should fail at the call site, not
        crash the control loop T_w seconds later."""
        mgr = self.engine.placement_mgr
        if mgr is None:
            raise ValueError("scale_out requires an elastic expert plane "
                             "(MoE + tarragon)")
        if not mgr.can_scale_out():
            raise ValueError(f"EW pool already at max_ew={mgr.max_ew}; "
                             "raise EngineConfig.max_ew to add spares")
        t_ready = now + self.T_w + self.T_push
        self._scales.append(_PendingScale("add_ew", -1, t_ready))
        self._emit(WorkerEvent(
            now, "scale_out_started", "ew?",
            f"join in T_w+T_push={self.T_w + self.T_push:.2f}s"))

    def request_scale_in(self, ew: int, now: float):
        """Drain an EW: its resident experts migrate to the survivors
        (weight push = T_push, during which it keeps serving the old
        plan), then it retires to spare."""
        mgr = self.engine.placement_mgr
        if mgr is None or ew not in mgr.members:
            raise ValueError(f"EW{ew} is not an elastic pool member")
        if len(mgr.members) <= 1:
            raise ValueError("cannot drain the last EW")
        self._scales.append(_PendingScale("drain_ew", ew, now + self.T_push))
        self._emit(WorkerEvent(
            now, "drain_started", f"ew{ew}",
            f"migrating experts, T_push={self.T_push:.2f}s"))

    def request_rebalance(self, now: float):
        if self.engine.placement_mgr is None:
            raise ValueError("rebalance requires an elastic expert plane "
                             "(MoE + tarragon)")
        self._scales.append(_PendingScale("rebalance", -1,
                                          now + self.T_push))
        self._emit(WorkerEvent(now, "rebalance_started", "pool",
                               f"T_push={self.T_push:.2f}s"))

    def _maybe_auto_rebalance(self, now: float):
        mgr = getattr(self.engine, "placement_mgr", None)
        if mgr is None or not self.auto_rebalance:
            return
        if now - self._last_rebalance < self.rebalance_cooldown:
            return
        if any(s.kind == "rebalance" for s in self._scales):
            return
        if self.engine.failed_ews:
            # mid-failure is the wrong moment to churn placement: wait for
            # revival/promotion to settle, then judge the real imbalance
            return
        if mgr.should_rebalance():
            self._last_rebalance = now
            self.request_rebalance(now)

    def _handle_detection(self, f: _PendingFailure,
                          now: float) -> WorkerEvent:
        """Self-healing for one failure detected at ``now``."""
        ev = WorkerEvent(now, "detected", f"{f.kind}{f.worker_id}")
        tel = getattr(self.engine, "telemetry", None)
        if tel is not None:
            # the detection window [t_fail, now] is the T_w component
            # of every stall this failure causes
            tel.on_failure_detected(f.kind, f.worker_id, f.t_fail, now)
        if f.kind == "ew":
            # AW-side self-healing: ERT remap to shadows (instant once
            # detected)
            with self._span("recovery.ew_remap"):
                self.engine.fail_ew(f.worker_id)
                promote = self.ew_policy == "promote" and \
                    self.engine.placement_mgr is not None
                if promote:
                    # permanent promotion: pool shrinks, shadows become
                    # primaries now; fresh replicas for the most critical
                    # survivor land after the background weight push
                    self.engine.promote_shadows(f.worker_id, now=now)
            if promote:
                ev.detail = "shadows promoted to primaries (pool -1)"
                self._provisions.append(_PendingProvision(
                    "reprotect", f.worker_id, now + self.T_push))
            else:
                ev.detail = "ERT remap -> shadow experts"
                self._provisions.append(
                    _PendingProvision(f.kind, f.worker_id,
                                      now + self.T_w))
        else:
            # EW-side self-healing: health mask drops the AW's slots;
            # per-request restoration re-admits its requests through
            # the Gateway (unplaceable ones stay queued and retry); each
            # restore is a recovery.restore span inside this one
            with self._span("recovery.aw_requeue"):
                self.engine.fail_aw(f.worker_id)
                n = len(self.engine.recover_aw_requests(now=now))
            ev.detail = f"restored {n} requests"
            waiting = self.engine.gateway.depth()
            if waiting:
                ev.detail += f" ({waiting} queued for retry)"
            self._provisions.append(
                _PendingProvision(f.kind, f.worker_id, now + self.T_w))
        self._emit(ev)
        return ev

    # -- control loop --------------------------------------------------------
    def tick(self, now: float) -> List[WorkerEvent]:
        """Advance the control plane to virtual time ``now``. Returns the
        events that fired during this tick."""
        fired: List[WorkerEvent] = []
        for f in self._failures:
            if f.detected or now < f.t_fail + self.detection_latency():
                continue
            f.detected = True
            with self._span("recovery.detect", kind=f.kind,
                            worker=f.worker_id):
                fired.append(self._handle_detection(f, now))

        remaining = []
        for p in self._provisions:
            if now < p.t_ready:
                remaining.append(p)
                continue
            if p.kind == "ew":
                # layer-aligned join (§5.4) + shadow re-pointing to protect
                # the placement manager's pick of most-load-critical EW
                # (background weight push) — no hardcoded neighbor. Still-
                # failed EWs are excluded both as protect target and from
                # replica recycling (their failover replicas stay pinned).
                dead = self.engine.failed_ews - {p.worker_id}
                protect = self.engine.choose_protect_ew(exclude=dead)
                if protect is None:
                    protect = (p.worker_id + 1) % max(
                        1, len(self.engine.ews))
                self.engine.provision_ew(p.worker_id,
                                         repoint_protect=protect, now=now)
                ev = WorkerEvent(now, "provisioned", f"ew{p.worker_id}",
                                 f"shadows protect ew{protect}")
            elif p.kind == "reprotect":
                protect = self.engine.choose_protect_ew(
                    exclude=self.engine.failed_ews)
                if protect is not None:
                    self.engine.repoint_shadows(protect, now=now)
                ev = WorkerEvent(now, "reprotected", f"ew{p.worker_id}",
                                 f"new replicas protect ew{protect}")
            else:
                self.engine.provision_aw(p.worker_id)
                # freshly provisioned capacity drains the waiting queue
                # (recovery entries sit at the front)
                self.engine.scheduler.admit(now)
                ev = WorkerEvent(now, "provisioned", f"aw{p.worker_id}")
            self._emit(ev)
            fired.append(ev)
        self._provisions = remaining

        remaining_s = []
        for s in self._scales:
            if now < s.t_ready:
                remaining_s.append(s)
                continue
            try:
                if s.kind == "add_ew":
                    new_ew = self.engine.add_ew(now=now)
                    # a scale-out invalidates the rebalance cooldown: the
                    # joiner starts empty, and a rebalance suppressed by a
                    # recent (pre-join) window would leave it idle for the
                    # rest of the cooldown — reset so the next auto pass
                    # may ship load to it immediately
                    self._last_rebalance = -1e30
                    ev = WorkerEvent(now, "scaled_out", f"ew{new_ew}",
                                     f"pool={sorted(self.engine.live_ews)}")
                elif s.kind == "drain_ew":
                    self.engine.drain_ew(s.worker_id, now=now)
                    ev = WorkerEvent(now, "scaled_in", f"ew{s.worker_id}",
                                     f"pool={sorted(self.engine.live_ews)}")
                else:
                    plan = self.engine.rebalance(now=now)
                    detail = f"gen{plan.generation}" if plan is not None \
                        else ""
                    ev = WorkerEvent(now, "rebalanced", "pool", detail)
            except ValueError as e:
                # the pool changed between request and completion (e.g. the
                # drain target died and was promoted away): surface it as an
                # event, don't kill the control loop
                ev = WorkerEvent(now, "scale_failed", s.kind, str(e))
            self._emit(ev)
            fired.append(ev)
        self._scales = remaining_s

        self._maybe_auto_rebalance(now)

        # surface placement-generation changes made by the engine this tick
        # (benchmarks/tests audit plan generations through the event log).
        # These were already published to the bus at emission — the drains
        # below only feed this legacy audit log, never the bus.
        for ev in self.engine.drain_plan_events() \
                if hasattr(self.engine, "drain_plan_events") else []:
            self.events.append(ev)
            fired.append(ev)
        # ... and request-lifecycle events (preempted/cancelled/
        # deadline_missed): the admission plane's timeline rides the same
        # audit log as the worker plane's
        for ev in self.engine.drain_request_events() \
                if hasattr(self.engine, "drain_request_events") else []:
            self.events.append(ev)
            fired.append(ev)
        return fired

    @property
    def outstanding(self) -> int:
        return len(self._provisions) + len(self._scales) + \
            sum(1 for f in self._failures if not f.detected)
