"""Serving launcher: run the Tarragon engine against a workload on a chosen
mesh/scale.

CPU-functional mode (default — this container):
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b \
        --workload random --rps 4 --duration 2 [--fail ew:0@0.5] \
        [--scale add_ew@1.0] [--scale drain_ew:2@3.0] [--max-ew 4] \
        [--ew-policy promote] [--rebalance]

The reduced model runs for real; failures are injected and recovered, and
the EW pool is elastic: scale events, load-aware rebalancing, and shadow
promotion are versioned placement-plan installs (core/placement.py).
``chip_smoke.py`` at the repository root drives the same ``build_server`` +
``serve`` path on one TPU at Mixtral-8x7B widths.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax

from repro.configs import get_config
from repro.core.orchestrator import Orchestrator
from repro.data.workloads import make_workload
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.scheduler import FailurePlan, ScalePlan, run_serving
from repro.serving.telemetry import pct


def parse_failure(s: str) -> FailurePlan:
    kindid, t = s.split("@")
    kind, wid = kindid.split(":")
    return FailurePlan(float(t), kind, int(wid))


def parse_scale(s: str) -> ScalePlan:
    """add_ew@T | drain_ew:ID@T | rebalance@T"""
    kindid, t = s.split("@")
    kind, _, wid = kindid.partition(":")
    if kind not in ("add_ew", "drain_ew", "rebalance"):
        raise ValueError(f"unknown scale kind {kind!r} in --scale {s!r} "
                         "(add_ew@T | drain_ew:ID@T | rebalance@T)")
    return ScalePlan(float(t), kind, int(wid) if wid else -1)


def dropless_capacity_factor(moe) -> float:
    """experts / top-k: the factor at which a slot's capacity
    (factor x top-k x tokens / experts) equals the tokens of the call.
    Mixtral's 8 / 2 is 4.0; Qwen3-30B-A3B's 128 / 8 is 16.0."""
    return moe.num_experts / moe.top_k


def build_server(cfg, ecfg: EngineConfig, *, seed: int = 0,
                 ew_policy: str = "revive", rebalance: bool = False):
    """The serving stack: one InferenceEngine (Gateway, AWs and EWs, the
    ERT/shadow-slot datapath, the checkpoint store) under an Orchestrator.

    MoE layers serve dropless, at ``dropless_capacity_factor``: each slot's
    capacity is the call's token count, and a token picks an expert once,
    so no expert slot overflows. The tokens a failure masks then cannot
    change how the surviving requests' tokens are routed, and token streams
    stay identical across failures. Returns (engine, orchestrator)."""
    if cfg.moe.enabled:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=dropless_capacity_factor(cfg.moe)))
    eng = InferenceEngine(cfg, ecfg, jax.random.PRNGKey(seed))
    orch = Orchestrator(eng, worker_init_time=1.0, weight_push_time=0.25,
                        ew_policy=ew_policy, auto_rebalance=rebalance)
    return eng, orch


def serve(eng: InferenceEngine, orch: Orchestrator, workload, *,
          failures=(), scales=()):
    """Drive ``workload`` through the serving loop on its virtual clock
    (fixed 50 ms decode ticks, so failure injection is deterministic)."""
    return run_serving(eng, workload, duration=600.0, orchestrator=orch,
                       failures=list(failures), scale_events=list(scales),
                       step_time=0.05)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral_8x7b")
    ap.add_argument("--workload",
                    choices=("random", "sharegpt", "skewed_expert_load",
                             "mixed_slo", "multi_turn_chat"),
                    default="random")
    ap.add_argument("--rps", type=float, default=4.0)
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--num-aw", type=int, default=2)
    ap.add_argument("--num-ew", type=int, default=2)
    ap.add_argument("--max-ew", type=int, default=0,
                    help="elastic EW pool ceiling (spares the orchestrator "
                         "can scale out into; 0 = num_ew)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--placement", default="least_loaded",
                    choices=("least_loaded", "round_robin",
                             "session_affinity"),
                    help="Gateway AW placement policy")
    ap.add_argument("--no-tarragon", action="store_true")
    ap.add_argument("--fail", type=str, action="append", default=[],
                    help="kind:worker@time, e.g. ew:0@0.5")
    ap.add_argument("--scale", type=str, action="append", default=[],
                    help="add_ew@T | drain_ew:ID@T | rebalance@T")
    ap.add_argument("--ew-policy", choices=("revive", "promote"),
                    default="revive",
                    help="EW failure handling: background revival, or "
                         "permanent shadow promotion (pool shrinks)")
    ap.add_argument("--rebalance", action="store_true",
                    help="auto-rebalance expert placement under load skew")
    ap.add_argument("--controller", action="store_true",
                    help="SLO-driven closed-loop control plane: EW "
                         "autoscaling, trajectory-triggered rebalance with "
                         "weighted splits, adaptive chunk budget, and "
                         "deadline-aware preemption (serving/controller.py)")
    ap.add_argument("--no-ctl-autoscale", action="store_true",
                    help="with --controller: disable the autoscale policy")
    ap.add_argument("--no-ctl-rebalance", action="store_true",
                    help="with --controller: disable the rebalance policy")
    ap.add_argument("--no-ctl-budget", action="store_true",
                    help="with --controller: disable the adaptive "
                         "chunk budget policy")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable preempt-and-requeue (blocked interactive "
                         "requests wait instead of evicting batch victims)")
    ap.add_argument("--prefix-slots", type=int, default=0,
                    help="per-AW prefix-cache slot budget (0 = plane off; "
                         "enables chunked prefill implicitly)")
    ap.add_argument("--chunk-budget", type=int, default=0,
                    help="chunked-prefill token budget per tick "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the telemetry plane (output is "
                         "bit-identical either way)")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto/Chrome trace_event JSON here")
    ap.add_argument("--postmortem", default="", metavar="PATH",
                    help="dump the flight-recorder postmortem bundle "
                         "(repro.postmortem.v1 JSON, replayable with "
                         "python -m repro.launch.replay) here at exit")
    ap.add_argument("--watchdogs", action="store_true",
                    help="continuous health watchdogs: leak/stall "
                         "regression detectors + invariant probes "
                         "(prints the health summary at exit)")
    ap.add_argument("--metrics-out", default="",
                    help="write the JSON metrics snapshot here")
    ap.add_argument("--prom-out", default="",
                    help="write the Prometheus text exposition here")
    args = ap.parse_args()
    if args.prefix_slots and not args.chunk_budget:
        args.chunk_budget = 16

    use_compile_cache()
    cfg = get_config(args.arch).reduced()
    if args.workload == "multi_turn_chat" and \
            args.placement == "least_loaded":
        args.placement = "session_affinity"
    ecfg = EngineConfig(max_batch=args.max_batch, max_seq=96,
                        num_aw=args.num_aw, num_ew=args.num_ew,
                        max_ew=args.max_ew,
                        tarragon=not args.no_tarragon,
                        placement=args.placement,
                        preempt=not args.no_preempt,
                        chunk_token_budget=args.chunk_budget,
                        prefill_token_cap=8 * args.chunk_budget,
                        prefix_cache_slots=args.prefix_slots,
                        telemetry=not args.no_telemetry,
                        trace_export_path=args.trace_out,
                        controller="on" if args.controller else "off",
                        ctl_autoscale=not args.no_ctl_autoscale,
                        ctl_rebalance=not args.no_ctl_rebalance,
                        ctl_chunk_budget=not args.no_ctl_budget,
                        victim_policy="controller" if args.controller and
                        not args.no_preempt else "remaining_work",
                        watchdogs=args.watchdogs)
    eng, orch = build_server(cfg, ecfg, seed=args.seed,
                             ew_policy=args.ew_policy,
                             rebalance=args.rebalance)

    wl = make_workload(args.workload, args.rps, args.duration,
                       seed=args.seed, max_prompt=16, max_new=24)
    m = serve(eng, orch, wl,
              failures=[parse_failure(f) for f in args.fail],
              scales=[parse_scale(s) for s in args.scale])

    tbt = m.tbt_values()
    print(f"[serve] {cfg.name} tarragon={not args.no_tarragon} "
          f"AW={args.num_aw} EW={args.num_ew} placement={args.placement}")
    print(f"  requests finished: {len(m.finished)}/{len(wl)}")
    print(f"  tokens: {len(m.token_log)}  "
          f"throughput: {m.throughput():.1f} tok/s")
    if tbt.size:
        print(f"  TBT p50={pct(tbt, 50)*1e3:.1f}ms "
              f"p95={pct(tbt, 95)*1e3:.1f}ms "
              f"max_stall={m.max_stall()*1e3:.1f}ms")
    qd = m.queue_delay_values()
    if qd.size:
        print(f"  queue delay p50={pct(qd, 50)*1e3:.1f}ms "
              f"p99={pct(qd, 99)*1e3:.1f}ms")
    if m.prefill:
        print(f"  prefill: {m.prefill['calls']} calls / "
              f"{m.prefill['requests']} reqs "
              f"occupancy={m.prefill['occupancy']:.2f}")
    if eng.placement_mgr is not None:
        mgr = eng.placement_mgr
        print(f"  expert plane: gen={mgr.plan.generation} "
              f"pool={sorted(eng.live_ews)} "
              f"imbalance={mgr.imbalance():.2f}")
    pf = m.gateway.get("prefix", {})
    if pf.get("hits") or pf.get("misses"):
        print(f"  prefix cache: {pf['hits']} hits, "
              f"{pf['hit_tokens']} tokens adopted, "
              f"{pf['restored']} restored, {pf['repins']} repins")
    if m.gateway.get("by_class"):
        print(f"  request plane: preemptions={m.gateway['preemptions']}")
        for cls, counts in sorted(m.gateway["by_class"].items()):
            ttft = m.ttft_values(cls)
            extra = f" ttft_p50={pct(ttft, 50)*1e3:.0f}ms" \
                if ttft.size else ""
            print(f"    {cls}: {counts}{extra}")
    for e in orch.events:
        print(f"  [orch t={e.t:.2f}] {e.kind} {e.worker} {e.detail}")
    if eng.controller is not None:
        for d in eng.controller.decisions:
            print(f"  [ctl t={d['t']:.2f}] {d['kind']} {d['detail']}")
    if m.telemetry is not None:
        for st in m.telemetry.stall_report():
            comps = ", ".join(f"{k}={v*1e3:.0f}ms"
                              for k, v in sorted(st["components"].items())
                              if v > 1e-6)
            print(f"  [stall {st['rid']} {st['kind']} "
                  f"{st['gap']*1e3:.0f}ms] {comps}")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(m.telemetry.snapshot(), f, indent=1)
            print(f"  metrics snapshot -> {args.metrics_out}")
        if args.prom_out:
            with open(args.prom_out, "w") as f:
                f.write(m.telemetry.prometheus_text())
            print(f"  prometheus text -> {args.prom_out}")
        if args.trace_out:
            print(f"  perfetto trace -> {args.trace_out} "
                  f"(open at ui.perfetto.dev)")
    fr = eng.flightrec
    if fr is not None and fr.watchdogs is not None:
        hs = fr.watchdogs.summary()
        print(f"  health: {hs['trips']} watchdog trip(s) over "
              f"{hs['intervals']} interval(s) {dict(hs['by_kind'])}")
        for t in hs["last_trips"]:
            print(f"    [health t={t['t']:.2f}] {t['kind']} "
                  f"{t['what']}: {t['detail']}")
    if args.postmortem and fr is not None:
        fr.dump(args.postmortem, reason="postmortem on demand (--postmortem)")
        print(f"  postmortem bundle -> {args.postmortem} "
              f"(replay: python -m repro.launch.replay {args.postmortem})")


if __name__ == "__main__":
    main()
