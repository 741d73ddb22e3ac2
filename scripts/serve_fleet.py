#!/usr/bin/env python
"""Serving fleet CLI: N supervised serve.py replicas + the front door.

One command turns a checkpoint (or params-only serving artifact) into
a fleet: each replica is a ``serve.py`` child wrapped in its own
resilience supervisor (crash ⇒ backoff restart, drained stop ⇒
budget-free preemption restart), and the router in front of them does
cache-aware placement, per-tenant weighted fair queueing, watermark
shedding (429 + Retry-After), health-based ejection/re-admission, and
SSE passthrough with cancel propagation (docs/FLEET.md).

    # three replicas behind one port; everything after -- goes to
    # each serve.py (e.g. scheduler knobs)
    python scripts/serve_fleet.py -r saved/.../model_best \\
        --replicas 3 --port 8900 -- --max-batch 8 --decode-chunk 4

    # front an already-running set of servers (no spawning)
    python scripts/serve_fleet.py --attach \\
        http://127.0.0.1:8001,http://127.0.0.1:8002

SIGTERM (or Ctrl-C) drains the whole fleet: the router stops, every
supervisor SIGTERM-drains its replica (serve.py finishes in-flight
requests and exits via the preemption path, rc 75), and the process
exits 0 with no orphans. ``--admin`` enables ``POST
/admin/kill|drain?replica=rN`` — the chaos/rolling-restart hooks the
tests use. Prints ``READY http://host:port`` once the router
is bound; replica readiness is visible on ``GET /healthz``.

Stdlib-only (the router manages jax processes, it is not one); run
evidence lands under ``--run-dir``: ``router.jsonl`` (lifecycle +
periodic counter snapshots — ``scripts/telemetry_report.py --fleet``
renders it) and per-replica ``rN/serve.log`` + ``rN/supervisor.jsonl``.
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from pytorch_distributed_template_tpu.fleet.admission import (  # noqa: E402
    staged_gates,
)
from pytorch_distributed_template_tpu.fleet.replicas import (  # noqa: E402
    FleetManager, Replica,
)
from pytorch_distributed_template_tpu.fleet.router import (  # noqa: E402
    HedgePolicy, RouterStats, build_router,
)
from pytorch_distributed_template_tpu.observability.reqtrace import (  # noqa: E402
    RequestTracer, SloWatcher,
)
from pytorch_distributed_template_tpu.observability.timeseries import (  # noqa: E402
    TimeSeriesStore, set_default_store,
)
from pytorch_distributed_template_tpu.resilience import faults  # noqa: E402
from pytorch_distributed_template_tpu.resilience.supervisor import (  # noqa: E402
    SupervisorConfig,
)


def parse_weights(spec: str) -> dict:
    """``"pro:4,free:1"`` -> ``{"pro": 4.0, "free": 1.0}``."""
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        try:
            out[name] = float(w or 1.0)
        except ValueError:
            raise SystemExit(f"--tenant-weights: bad entry {part!r} "
                             "(want name:weight)")
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="fleet front door: cache-aware router over N "
                    "supervised serve.py replicas",
        epilog="arguments after -- are passed to every serve.py")
    p.add_argument("-r", "--resume", default=None,
                   help="checkpoint / serving artifact every replica "
                        "serves (required unless --attach)")
    p.add_argument("-c", "--config", default=None,
                   help="config passed through to serve.py")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--attach", default=None, metavar="URL[,URL...]",
                   help="front these already-running servers instead "
                        "of spawning replicas")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8900,
                   help="router port (0 picks a free one, printed on "
                        "READY)")
    p.add_argument("--run-dir", default="fleet_run",
                   help="router.jsonl + per-replica logs/events")
    # placement
    p.add_argument("--policy", default="cache_aware",
                   choices=("cache_aware", "least_loaded",
                            "round_robin"))
    p.add_argument("--block-tokens", type=int, default=32,
                   help="affinity-radix block size — match the "
                        "replicas' serving.prefix_cache.block_tokens")
    p.add_argument("--load-spread", type=float, default=4.0,
                   help="cache-aware: fall back to least-loaded when "
                        "the prefix-holding replica's queue estimate "
                        "exceeds the lightest one's by more than this")
    # disaggregated prefill/decode (ISSUE 12)
    p.add_argument("--roles", default="", metavar="ROLE[,ROLE...]",
                   help="assign serving roles to spawned replicas "
                        "cyclically, e.g. 'prefill,decode' gives r0 "
                        "--role prefill and r1 --role decode (each "
                        "also gets --prefix-cache on — role-split "
                        "serving ships pool pages). With a dedicated "
                        "prefill replica live, the router brokers "
                        "prefill→decode page handoffs with a second "
                        "independent admission queue; empty (default) "
                        "keeps the classic colocated fleet")
    p.add_argument("--disagg-min-ids", type=int, default=32,
                   help="smallest affinity-id count (prompt_ids, or "
                        "UTF-8 bytes of a text prompt) worth a page "
                        "handoff; shorter prompts route colocated")
    p.add_argument("--prefill-queue-timeout-s", type=float, default=0.0,
                   help="prefill-stage waiters older than this fall "
                        "back to the colocated path (0 = the decode "
                        "gate's --queue-timeout-s)")
    # admission / backpressure
    p.add_argument("--queue-factor", type=float, default=2.0,
                   help="per-replica oversubscription: fleet capacity "
                        "= healthy slots x this")
    p.add_argument("--max-waiting", type=int, default=64,
                   help="waiting-room watermark: requests past it "
                        "shed with 429 + Retry-After")
    p.add_argument("--queue-timeout-s", type=float, default=30.0,
                   help="waiters older than this shed (429)")
    p.add_argument("--tenant-weights", default="",
                   metavar="NAME:W,...",
                   help="weighted fair queueing weights per X-Tenant "
                        "value (default 1.0 each)")
    # health
    p.add_argument("--peer-pull", default="off",
                   choices=("on", "off"),
                   help="miss-driven peer page migration (ISSUE 13): "
                        "a request routed to a replica whose prefix "
                        "lives on a peer pulls the peer's pool pages "
                        "(/export_pages -> /admit_pages) before "
                        "dispatch instead of recomputing the prefill; "
                        "failures/timeouts degrade to a cold prefill")
    p.add_argument("--peer-pull-min-tokens", type=int, default=64,
                   help="smallest extra cached-token depth on a peer "
                        "worth a pull")
    p.add_argument("--peer-pull-timeout-s", type=float, default=5.0,
                   help="per-hop timeout for peer page pulls")
    p.add_argument("--rewarm", default="off", choices=("on", "off"),
                   help="restart re-warm (ISSUE 13): a killed/ejected "
                        "replica's hottest prefixes (snapshotted from "
                        "the placement radix at ejection) replay from "
                        "peers BEFORE readmission, so it rejoins warm "
                        "instead of cold")
    p.add_argument("--rewarm-top-k", type=int, default=8,
                   help="how many hot prefixes the re-warm replays")
    p.add_argument("--poll-s", type=float, default=1.0)
    p.add_argument("--eject-after", type=int, default=2,
                   help="consecutive failed health polls before a "
                        "replica stops receiving traffic")
    p.add_argument("--readmit-after", type=int, default=2)
    p.add_argument("--wedge-after", type=int, default=0,
                   help="consecutive polls of frozen scheduler "
                        "progress (with pending work, /healthz still "
                        "answering) before a replica is ejected as "
                        "WEDGED and SIGKILL-restarted (ISSUE 9). "
                        "0 (default) derives a ~60 s window from "
                        "--poll-s — generous on purpose: mid-life XLA "
                        "compiles freeze the counter legitimately; "
                        "tighten only with warmed ladders "
                        "(--warm-buckets)")
    p.add_argument("--no-restart-wedged", action="store_true",
                   help="eject wedged replicas without the SIGKILL "
                        "restart (attach mode / debugging)")
    # hedged requests (ISSUE 9, non-streaming only)
    p.add_argument("--hedge", default="off", choices=("on", "off"),
                   help="hedged requests: after the p95-based delay "
                        "an unanswered non-streaming request fires at "
                        "a second replica, first response wins, the "
                        "loser is cancelled upstream")
    p.add_argument("--hedge-frac", type=float, default=0.05,
                   help="hedge budget: at most this fraction of "
                        "requests may hedge (Tail-at-Scale ~5%%)")
    p.add_argument("--hedge-delay-ms", type=float, default=0.0,
                   help="fixed hedge delay; 0 derives p95 from the "
                        "router's own e2e histogram per request")
    # deterministic fault injection (ISSUE 9; resilience/faults.py)
    p.add_argument("--router-faults", default="",
                   help="PDT_FAULTS-grammar plan for the ROUTER "
                        "process (proxy_latency@req:N[:ms], "
                        "proxy_blackhole@req:N)")
    p.add_argument("--replica-faults", action="append", default=[],
                   metavar="RID=PLAN",
                   help="per-replica fault plan, exported as "
                        "PDT_FAULTS into THAT child only (e.g. "
                        "r1=hang@tick:5); repeatable")
    # replica supervision
    p.add_argument("--max-restarts", type=int, default=10)
    p.add_argument("--restart-delay", type=float, default=1.0,
                   metavar="S")
    p.add_argument("--read-timeout-s", type=float, default=600.0,
                   help="per-request upstream read timeout")
    p.add_argument("--admin", action="store_true",
                   help="enable POST /admin/kill and /admin/drain "
                        "(chaos injection, rolling restarts)")
    # request tracing + SLO (observability/reqtrace.py)
    p.add_argument("--reqtrace", default="on", choices=("on", "off"),
                   help="request-scoped span tracing: the router "
                        "mints/propagates X-Request-Id and appends "
                        "its spans to <run-dir>/spans.jsonl "
                        "(scripts/trace_stitch.py merges them with "
                        "the replicas' into one cross-process trace)")
    p.add_argument("--slo-ttft-s", type=float, default=0.0,
                   help="router-observed TTFT SLO threshold (streamed "
                        "requests): breaches bump slo_breach_total on "
                        "/metrics + bounded slow-request dumps under "
                        "--run-dir (0 = off)")
    p.add_argument("--slo-e2e-s", type=float, default=0.0,
                   help="router-observed end-to-end SLO threshold "
                        "(0 = off)")
    # autoscaler (ISSUE 19)
    p.add_argument("--autoscale", default="off", choices=("on", "off"),
                   help="run the fleet autoscaler: the poller-scraped "
                        "pressure signals (queue depth, brownout "
                        "level, SLO-breach EWMA, arrival-rate trend) "
                        "drive replica spawn/drain through the SAME "
                        "policy the simulator replays offline "
                        "(fleet/autoscaler.py); spawned replicas are "
                        "built by the exact construction path the "
                        "launch replicas used")
    p.add_argument("--min-replicas", type=int, default=1,
                   help="autoscaler floor (never drains below)")
    p.add_argument("--max-replicas", type=int, default=0,
                   help="autoscaler ceiling (0 = 2x --replicas)")
    p.add_argument("--autoscale-interval-s", type=float, default=1.0,
                   help="policy tick period")
    p.add_argument("--scale-up-pressure", type=float, default=0.85,
                   help="effective pressure above this spawns "
                        "ceil(replicas*pressure/threshold) - replicas "
                        "more replicas (multi-step, capped)")
    p.add_argument("--scale-down-pressure", type=float, default=0.40,
                   help="pressure must sit at or below this for "
                        "--scale-down-dwell-s before a drain")
    p.add_argument("--scale-up-cooldown-s", type=float, default=5.0)
    p.add_argument("--scale-down-cooldown-s", type=float, default=20.0)
    p.add_argument("--scale-down-dwell-s", type=float, default=10.0,
                   help="hysteresis dwell: low pressure must HOLD "
                        "this long (plus the cooldown) — scale-down "
                        "never flaps on a transient dip")
    p.add_argument("--scale-horizon-s", type=float, default=20.0,
                   help="predictive scale-ahead: provision for the "
                        "arrival rate this far ahead on the current "
                        "trend (0 disables prediction)")
    p.add_argument("--autoscale-roles", default="off",
                   choices=("on", "off"),
                   help="let the policy flip replica roles "
                        "(both<->prefill) on request-mixture shift; "
                        "flips are replace-then-retire: the old role "
                        "drains only after its replacement is healthy")
    p.add_argument("--autoscale-rewarm-top-k", type=int, default=8,
                   help="fleet-hot prefixes proactively replayed into "
                        "a scaled-up replica via the re-warm path "
                        "before it takes traffic (0 = spawn cold)")
    # fleet timeline store (ISSUE 14)
    p.add_argument("--timeline", default="on", choices=("on", "off"),
                   help="fleet time-series store: the poller folds "
                        "each sweep's counters into rate points "
                        "(<run-dir>/timeseries.jsonl), feeding the "
                        "/dashboard sparklines and the autoscaling "
                        "measurement substrate")
    p.add_argument("--timeline-interval-s", type=float, default=0.0,
                   help="time-series point width (0 = --poll-s)")
    return p


def parse_replica_faults(entries) -> dict:
    """``["r1=hang@tick:5", ...]`` -> ``{"r1": "hang@tick:5"}``,
    validating each plan through the fault grammar NOW (a typo should
    fail in milliseconds, not silently never fire in a chaos run)."""
    from pytorch_distributed_template_tpu.resilience.faults import (
        FaultPlan,
    )

    out = {}
    for entry in entries or []:
        rid, sep, plan = entry.partition("=")
        if not sep or not rid.strip():
            raise SystemExit(
                f"--replica-faults: bad entry {entry!r} "
                "(want RID=PLAN)")
        try:
            FaultPlan.parse(plan)
        except ValueError as e:
            raise SystemExit(f"--replica-faults {rid}: {e}")
        out[rid.strip()] = plan
    return out


def main(argv=None) -> int:
    args, rest = build_parser().parse_known_args(argv)
    if rest and rest[0] == "--":
        rest = rest[1:]
    run_dir = Path(args.run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    replica_faults = parse_replica_faults(args.replica_faults)
    if args.router_faults:
        # the router's own plan (proxy_* kinds). configure() lets an
        # operator-level PDT_FAULTS env override this — but that env
        # would ALSO be inherited by every replica child, so the CLI
        # flags are the per-process way to aim faults.
        faults.configure(args.router_faults)
    make_replica = None
    if args.attach:
        urls = [u.strip() for u in args.attach.split(",") if u.strip()]
        replicas = [Replica(f"r{i}", url=u)
                    for i, u in enumerate(urls)]
    else:
        if not args.resume:
            print("serve_fleet: need -r/--resume (or --attach)",
                  file=sys.stderr)
            return 2
        serve_py = REPO / "serve.py"
        roles = [r.strip() for r in (args.roles or "").split(",")
                 if r.strip()]
        for role in roles:
            if role not in ("both", "prefill", "decode"):
                print(f"serve_fleet: unknown role {role!r} in --roles",
                      file=sys.stderr)
                return 2

        def make_replica(rid: str, role: str = "both") -> Replica:
            """ONE construction path for every replica, initial or
            scaled-up (ISSUE 19): the autoscaler's spawns are built
            from exactly the flags the launch replicas got."""
            cmd = [sys.executable, str(serve_py), "-r", args.resume,
                   "--host", "127.0.0.1", "--port", "0",
                   "-s", str(run_dir / rid / "save")]
            if role != "both":
                # role-split serving IS the pool: force it on so the
                # replica can export/import pages
                cmd += ["--role", role, "--prefix-cache", "on"]
            if args.config:
                cmd += ["-c", args.config]
            # replicas inherit the fleet's SLO/tracing posture (the
            # ISSUE 8 contract puts slo_breach_total on BOTH router
            # and replica /metrics); explicit flags after -- still win
            if args.slo_ttft_s:
                cmd += ["--slo-ttft-s", str(args.slo_ttft_s)]
            if args.slo_e2e_s:
                cmd += ["--slo-e2e-s", str(args.slo_e2e_s)]
            if args.reqtrace == "off":
                cmd += ["--reqtrace", "off"]
            cmd += rest
            # per-replica fault plans ride the child env (ISSUE 9):
            # one replica gets its chaos while siblings run clean; a
            # rid with no plan explicitly CLEARS any inherited
            # PDT_FAULTS so an operator-level plan cannot leak into
            # every child at once
            child_env = {"PDT_FAULTS": replica_faults.get(rid, "")} \
                if replica_faults else None
            return Replica(
                rid, cmd=cmd, run_dir=run_dir, role=role,
                sup_cfg=SupervisorConfig(
                    max_restarts=args.max_restarts,
                    restart_delay_s=args.restart_delay,
                    max_delay_s=30.0, poll_s=0.2,
                    stable_runtime_s=120.0,
                    child_env=child_env))

        replicas = [
            make_replica(f"r{i}",
                         roles[i % len(roles)] if roles else "both")
            for i in range(max(args.replicas, 1))]
    # fleet timeline store (ISSUE 14): one rate/gauge point per poll
    # sweep into <run-dir>/timeseries.jsonl — the /dashboard
    # sparklines and the autoscaling substrate read it. Registered as
    # the process default so forensic dumps carry the trend window.
    tsdb = None
    stats = RouterStats()
    if args.timeline != "off":
        tsdb = TimeSeriesStore(
            run_dir / "timeseries.jsonl",
            interval_s=(args.timeline_interval_s
                        or max(args.poll_s, 0.25)),
            process="router")
        set_default_store(tsdb)

    def _tsdb_extra() -> dict:
        # router-side series the manager cannot see: admission
        # depths, shed counters, and the goodput ledger
        flat = dict(stats.snapshot())
        flat.update(admission.depths())
        adm = admission.stats()
        flat["admitted_total"] = adm["admitted"]
        flat["shed_total"] = adm["shed_total"]
        flat["brownout_shed_total"] = adm["brownout_shed_total"]
        gp = stats.goodput.stats()
        gp.pop("goodput_tenants", None)
        flat.update(gp)
        return flat

    manager = FleetManager(
        replicas, run_dir=run_dir, policy=args.policy,
        block_tokens=args.block_tokens,
        min_match_tokens=args.block_tokens,
        load_spread=args.load_spread, poll_s=args.poll_s,
        eject_after=args.eject_after,
        readmit_after=args.readmit_after,
        queue_factor=args.queue_factor,
        wedge_after=(args.wedge_after or None),
        restart_wedged=not args.no_restart_wedged,
        peer_pull=args.peer_pull == "on",
        peer_pull_min_tokens=args.peer_pull_min_tokens,
        peer_pull_timeout_s=args.peer_pull_timeout_s,
        rewarm=args.rewarm == "on",
        rewarm_top_k=args.rewarm_top_k,
        tsdb=tsdb,
        tsdb_extra_fn=(_tsdb_extra if tsdb is not None else None))
    # two-stage admission (ISSUE 12): the front door's gate caps the
    # DECODE stage and a second, clock-independent gate wraps only the
    # prefill hop of each handoff. Both capacity fns are ROLE-FILTERED
    # unconditionally: in an all-"both" fleet every replica serves
    # both stages, so they equal the classic full capacity — while an
    # attach-mode fleet whose roles are only DISCOVERED by the poller
    # (the configured Replica objects all start "both") still gets the
    # right split the moment /metrics reports real roles.
    admission, prefill_admission = staged_gates(
        lambda: manager.capacity(role="decode"),
        prefill_capacity_fn=lambda: manager.capacity(role="prefill"),
        weights=parse_weights(args.tenant_weights),
        max_waiting=args.max_waiting,
        queue_timeout_s=args.queue_timeout_s,
        prefill_queue_timeout_s=(args.prefill_queue_timeout_s or None))

    # recoveries must re-open the gate for queued waiters immediately
    def _on_capacity():
        admission.kick()
        if prefill_admission is not None:
            prefill_admission.kick()

    manager.on_capacity_change = _on_capacity
    # request tracing + SLO plumbing (ISSUE 8): the router is the
    # first hop — it mints X-Request-Id, records admission-wait and
    # proxy-hop spans to <run-dir>/spans.jsonl, and checks TTFT/e2e
    # SLOs against the thresholds (bounded slow_request_<rid>.json
    # dumps land in --run-dir, counters on /metrics)
    tracer = (RequestTracer(run_dir / "spans.jsonl", process="router")
              if args.reqtrace != "off" else None)
    slo = SloWatcher(ttft_s=args.slo_ttft_s, e2e_s=args.slo_e2e_s,
                     dump_dir=run_dir, tracer=tracer)
    hedge = HedgePolicy(enabled=args.hedge == "on",
                        frac=args.hedge_frac,
                        delay_ms=args.hedge_delay_ms)

    # autoscaler (ISSUE 19): the live half of the sim/live policy
    # pair. Only meaningful when WE own replica construction — attach
    # mode has no way to spawn more of someone else's servers.
    autoscaler = None
    if args.autoscale == "on" and make_replica is not None:
        from pytorch_distributed_template_tpu.fleet.autoscaler import (
            Autoscaler, AutoscaleConfig, AutoscalePolicy)
        as_cfg = AutoscaleConfig(
            min_replicas=max(args.min_replicas, 1),
            max_replicas=(args.max_replicas
                          or 2 * max(args.replicas, 1)),
            up_pressure=args.scale_up_pressure,
            down_pressure=args.scale_down_pressure,
            up_cooldown_s=args.scale_up_cooldown_s,
            down_cooldown_s=args.scale_down_cooldown_s,
            down_dwell_s=args.scale_down_dwell_s,
            horizon_s=args.scale_horizon_s,
            role_flip=args.autoscale_roles == "on")
        autoscaler = Autoscaler(
            manager, AutoscalePolicy(as_cfg), make_replica,
            interval_s=args.autoscale_interval_s,
            rewarm_top_k=args.autoscale_rewarm_top_k)
        # the autoscaler's gauges ride the manager's counter snapshot
        # onto the router's /metrics (merged outside the fleet lock)
        manager.extra_counters_fn = autoscaler.stats

    server = build_router(manager, admission, host=args.host,
                          port=args.port, stats=stats,
                          allow_admin=args.admin,
                          read_timeout_s=args.read_timeout_s,
                          tracer=tracer, slo=slo, hedge=hedge,
                          prefill_admission=prefill_admission,
                          disagg_min_ids=args.disagg_min_ids,
                          tsdb=tsdb, autoscaler=autoscaler)

    draining = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001
        if draining.is_set():
            return
        draining.set()
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    manager.start()
    if autoscaler is not None:
        autoscaler.start()
    host, port = server.server_address[:2]
    print(f"READY http://{host}:{port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    # drain: every supervisor SIGTERMs its replica (serve.py finishes
    # in-flight work, exits rc 75), threads join, no orphans
    if autoscaler is not None:
        autoscaler.stop()
    manager.stop()
    server.server_close()
    print("DRAINED", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
