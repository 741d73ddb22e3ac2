#!/usr/bin/env python
"""Offline telemetry analyzer + service-model drift gate.

Turns a run's observability artifacts — ``telemetry.jsonl`` (flight
recorder), ``trace.json`` (host spans), ``anomaly_*.json`` (numerics
forensics) — into one report:

    # human/markdown report over a run dir
    python scripts/telemetry_report.py --run-dir saved/<exp>/train/<id>

Report fields (JSON with ``--json``, markdown otherwise):

- steady-state steps/s, tokens/s, examples/s — computed over timed
  records EXCLUDING the first step and any record carrying
  ``compile_events`` (compilation is startup cost, not throughput);
- mean MFU over the records that report it;
- data-wait fraction (summed ``data_wait_ms`` / summed ``wall_ms``) —
  the "is this run input-bound?" number;
- compile-cache hit rate from the per-record cache hit/miss events;
- set-up by phase (the first record's ``setup``: seconds inside each
  of the trainer's set-up spans) and stalled iterations (records with
  ``stall``: count, the worst, and the tally of where they went);
- anomaly count + straggler windows + per-host wall spread (from the
  health layer's recorder events and ``hosts{}`` aggregates);
- supervisor restart counters (``--supervisor supervisor.jsonl`` or a
  ``supervisor.jsonl`` inside ``--run-dir``): restarts by cause
  (crash/hang/preemption), give-up reason, clean completion;
- fleet front-door lifecycle (``--fleet router.jsonl`` or one inside
  ``--run-dir``): routed-by-policy counters, prefix-routed fraction,
  shed/dispatch errors, ejections/re-admissions with recovery times,
  and whether the fleet drained clean (no orphans);
- top host spans by total time (from ``trace.json``).

``--drift CURRENT BASELINE`` (ISSUE 14) is the DISTRIBUTION-level
gate: two ``service_model.json`` files (observability/servicedist.py)
compared per segment on p50/p99 with a relative
``--drift-tolerance`` — exit 1 on any shift in EITHER direction, so a
p99 regression in ``admit`` fails CI even when aggregate tok/s held.
A model self-compares clean at tolerance 0. Exit codes: 0 ok, 1 drift,
2 usage or unreadable input.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# ---------------------------------------------------------------------------
# input loading
# ---------------------------------------------------------------------------


def load_jsonl(path) -> list:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # a torn tail line (crash mid-write) is expected
    return records


# ---------------------------------------------------------------------------
# analyzers
# ---------------------------------------------------------------------------


def analyze_telemetry(records: list) -> dict:
    """Aggregate a flight-recorder timeline (see module doc)."""
    out: dict = {"records": len(records)}
    timed = [r for r in records if r.get("wall_ms")]
    # steady state: drop the first timed record (compile / warm-install)
    # and anything that carries compile events — those steps measure XLA,
    # not the model
    steady = [r for r in timed[1:] if not r.get("compile_events")]
    out["steady_steps"] = len(steady)
    if steady:
        wall_s = sum(r["wall_ms"] for r in steady) / 1e3
        out["steady_steps_per_sec"] = round(len(steady) / wall_s, 4)
        tokens = sum(r.get("tokens", 0) for r in steady)
        if tokens:
            out["steady_tokens_per_sec"] = round(tokens / wall_s, 1)
        examples = sum(r.get("examples", 0) for r in steady)
        if examples:
            out["steady_examples_per_sec"] = round(examples / wall_s, 1)
        waits = [r["data_wait_ms"] for r in steady
                 if r.get("data_wait_ms") is not None]
        if waits:
            out["data_wait_frac"] = round(
                sum(waits) / (wall_s * 1e3), 4
            )
    mfus = [r["mfu"] for r in records if r.get("mfu") is not None]
    if mfus:
        out["mfu_mean"] = round(sum(mfus) / len(mfus), 4)
    losses = [r["loss"] for r in records if r.get("loss") is not None]
    if losses:
        out["last_loss"] = losses[-1]
    # compile picture: event counts + persistent-cache hit rate
    compiles = hits = misses = 0
    compile_ms = 0.0
    for r in records:
        for ev in r.get("compile_events") or []:
            name = ev.get("event", "")
            if name.endswith("cache_hits"):
                hits += 1
            elif name.endswith("cache_misses"):
                misses += 1
            elif "dur_ms" in ev:
                compiles += 1
                compile_ms += ev["dur_ms"]
    out["compile_events"] = compiles
    if compiles:
        out["compile_ms_total"] = round(compile_ms, 1)
    if hits + misses:
        out["compile_cache_hit_rate"] = round(hits / (hits + misses), 3)
    # health layer: anomaly / profile events, straggler windows, spread
    out["anomalies"] = sum(
        1 for r in records if r.get("event") == "anomaly"
    )
    out["profile_captures"] = sum(
        1 for r in records if r.get("event") == "profile_capture"
    )
    straggler_windows = [r for r in records if r.get("straggler")]
    out["straggler_windows"] = len(straggler_windows)
    spreads = [r["wall_spread"] for r in records
               if r.get("wall_spread") is not None]
    if spreads:
        out["host_wall_spread_max"] = max(spreads)
        hosts = next(
            (r["hosts"] for r in reversed(records) if r.get("hosts")),
            None,
        )
        if hosts:
            out["hosts"] = len(hosts)
    rss = [r["host_rss_mb"] for r in records if r.get("host_rss_mb")]
    if rss:
        out["host_rss_mb_max"] = max(rss)
    hbm_peak = 0
    for r in records:
        for stats in (r.get("devices") or {}).values():
            hbm_peak = max(hbm_peak, int(stats.get("peak_bytes_in_use", 0)))
    if hbm_peak:
        out["hbm_peak_mb"] = round(hbm_peak / 2**20, 1)
    # set-up by phase: the trainer's first record says where its own
    # start went (seconds inside each span; the warm-up's stages ran on
    # their own thread, beside the rest)
    setup = next((r["setup"] for r in records
                  if isinstance(r.get("setup"), dict)), None)
    if setup:
        out["setup"] = dict(setup)
    # stalled iterations: how many, the worst, and where they went
    stalled = [r for r in records if isinstance(r.get("stall"), dict)]
    if stalled:
        worst = max(stalled, key=lambda r: r["stall"].get("over_ms", 0))
        stalls = {"count": len(stalled),
                  "worst_over_ms": worst["stall"].get("over_ms"),
                  "worst_step": worst.get("step"),
                  "worst_gc_ms": worst["stall"].get("gc_ms")}
        for r in stalled:
            key = f"in {r['stall'].get('in')}"
            stalls[key] = stalls.get(key, 0) + 1
        out["stalls"] = stalls
    return out


def analyze_prefix(records: list) -> dict:
    """Serving prefix-cache section from the slot engine's per-chunk
    ``serve_chunk`` records (engine/continuous._absorb): the counters
    are cumulative, so totals come from the LAST record; pool pressure
    is the max occupancy seen. Empty when the run served nothing (or
    predates the prefix cache)."""
    serve = [r for r in records if r.get("event") == "serve_chunk"]
    if not serve:
        return {}
    last = serve[-1]
    out: dict = {"serve_chunks": len(serve)}
    for k in ("tokens_generated_total", "admissions_total",
              "prefix_hit_tokens_total", "prefix_hit_requests_total",
              "prefix_lookups_total", "prefix_evictions_total",
              "prefix_pool_blocks",
              # ISSUE 7 paged-decode observability: warm-admit device
              # copy bytes (paged path: 0 — the zero-copy claim as a
              # counter, not a slogan), the fraction of decode chunks
              # served by the paged path, zero-copy radix adoptions,
              # and the resident-vs-referenced occupancy split that
              # stops hot prefixes double-counting
              "warm_admit_copy_bytes_total", "paged_decode_frac",
              "prefix_adopted_blocks_total",
              "prefix_pool_blocks_resident",
              "prefix_pool_blocks_referenced",
              # long-context serving (ISSUE 15): chunked streaming
              # prefill progress and WHY traffic degraded off the
              # paged pool (pool_fallback_total — the per-reason split
              # lives on /metrics; the refusal string used to go to
              # logs only)
              "prefill_chunks_total", "streamed_prefill_tokens_total",
              "pool_fallback_total"):
        if last.get(k) is not None:
            out[k] = last[k]
    lookups = out.get("prefix_lookups_total")
    if lookups:
        out["prefix_hit_rate"] = round(
            out.get("prefix_hit_requests_total", 0) / lookups, 3)
    used = [r["prefix_pool_blocks_used"] for r in serve
            if r.get("prefix_pool_blocks_used") is not None]
    if used:
        out["prefix_pool_used_max"] = max(used)
    return out


def analyze_tp(records: list) -> dict:
    """Tensor-parallel serving section (ISSUE 10) from the slot
    engine's per-chunk ``serve_chunk`` records: the TP degree and the
    per-decode-step collective accounting (compiled-HLO counted,
    engine-side constant — the LAST record is authoritative), plus the
    analytic floor beside it.
    Empty for single-chip runs (tp fields absent)."""
    serve = [r for r in records if r.get("event") == "serve_chunk"
             and r.get("tp_degree")]
    if not serve:
        return {}
    last = serve[-1]
    out = {"tp_degree": last["tp_degree"]}
    for k in ("tp_collective_count_per_step",
              "tp_collective_bytes_per_step",
              "tp_collective_floor_bytes"):
        if last.get(k) is not None:
            out[k] = last[k]
    floor = out.get("tp_collective_floor_bytes")
    got = out.get("tp_collective_bytes_per_step")
    if floor and got:
        out["tp_bytes_vs_floor"] = round(got / floor, 3)
    return out


def analyze_trace(path, top: int = 8) -> dict:
    """Total host-span time by name from a Chrome trace-event file."""
    try:
        events = json.loads(Path(path).read_text()).get("traceEvents", [])
    except (OSError, json.JSONDecodeError, AttributeError):
        return {}
    totals: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        t = totals.setdefault(e.get("name", "?"), [0.0, 0])
        t[0] += e.get("dur", 0.0) / 1e3
        t[1] += 1
    spans = sorted(totals.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "events": len(events),
        "top_spans": [
            {"name": n, "total_ms": round(ms, 1), "count": c}
            for n, (ms, c) in spans
        ],
    }


def analyze_supervisor(path) -> dict:
    """Fold a ``supervisor.jsonl`` lifecycle log (resilience
    subsystem) into restart counters: how many relaunches, why, and
    whether the supervisor gave up or finished clean. One parser owns
    the schema — ``resilience.supervisor.read_supervisor_stats`` (also
    behind serve.py's /metrics and the CI chaos gate) — and this only
    flattens its result for the markdown table."""
    from pytorch_distributed_template_tpu.resilience.supervisor import (
        read_supervisor_stats,
    )

    stats = read_supervisor_stats(path)
    out: dict = {
        "restarts_total": stats["restarts_total"],
        "attempts": stats["attempts"],
        "clean": stats["clean"],
        "gave_up": stats["gave_up"],
    }
    if stats["last_restart_cause"] is not None:
        out["last_restart_cause"] = stats["last_restart_cause"]
    for cause, n in sorted(stats["causes"].items()):
        out[f"cause_{cause}"] = n
    return out


def analyze_fleet(path) -> dict:
    """Fold a fleet router's ``router.jsonl`` (fleet/replicas.py
    EventLog: lifecycle events + periodic counter snapshots) into the
    operator's questions: how much traffic, how much shed, how was it
    routed, how many ejections/recoveries and how fast, and did the
    fleet drain clean."""
    counts: dict = {}
    last_snapshot: dict = {}
    recoveries = []
    orphans = None
    for rec in load_jsonl(path):
        ev = rec.get("event")
        counts[ev] = counts.get(ev, 0) + 1
        if ev == "snapshot":
            last_snapshot = rec
        elif ev == "readmit" and rec.get("recovery_s") is not None:
            recoveries.append(float(rec["recovery_s"]))
        elif ev == "stopped":
            orphans = rec.get("orphans")
    out: dict = {
        "replicas": last_snapshot.get("replicas"),
        "replicas_healthy": last_snapshot.get("replicas_healthy"),
        "ejections": counts.get("eject", 0),
        "readmissions": counts.get("readmit", 0),
        "kills": counts.get("kill", 0),
        "rolling_drains": counts.get("drain_replica", 0),
        "drained_clean": (None if orphans is None else orphans == 0),
    }
    for key in ("routed_prefix_total", "routed_least_loaded_total",
                "routed_round_robin_total", "dispatch_errors_total",
                "fleet_requests_total", "fleet_prefix_hit_tokens_total",
                "fleet_tokens_generated_total",
                # token-integrity auditing (ISSUE 18): the fleet-level
                # verdict counters — any nonzero divergence in a run's
                # last snapshot belongs in the report headline
                "fleet_audit_sampled_total",
                "fleet_token_divergence_total",
                "fleet_audit_dropped_total"):
        if key in last_snapshot:
            out[key] = last_snapshot[key]
    if last_snapshot.get("fleet_audit_sampled_total"):
        out["audit_clean"] = not last_snapshot.get(
            "fleet_token_divergence_total")
    routed = sum(out.get(k, 0) or 0
                 for k in ("routed_prefix_total",
                           "routed_least_loaded_total",
                           "routed_round_robin_total"))
    if routed:
        out["prefix_routed_frac"] = round(
            (out.get("routed_prefix_total", 0) or 0) / routed, 4)
    if recoveries:
        out["recovery_s_mean"] = round(
            sum(recoveries) / len(recoveries), 3)
        out["recovery_s_max"] = round(max(recoveries), 3)
    return {k: v for k, v in out.items() if v is not None}


def analyze_disagg(path) -> dict:
    """Disaggregated-serving section (ISSUE 12) from the router's
    ``router.jsonl`` counter snapshots: how many prefill→decode page
    handoffs the router brokered, the page/byte volume that crossed
    (PR 10's collective-accounting discipline: measured transfer, not
    an estimate), the handoff latency p50/p99, the effective transfer
    rate, per-role healthy-replica counts, and how often an eligible
    request fell back to the colocated path. Empty on a fleet that
    never disaggregated — the section only renders when the feature
    ran."""
    last_snapshot: dict = {}
    first_t = last_t = None
    for rec in load_jsonl(path):
        if rec.get("event") == "snapshot":
            last_snapshot = rec
        t = rec.get("t")
        if isinstance(t, (int, float)):
            first_t = t if first_t is None else first_t
            last_t = t
    if not last_snapshot.get("handoffs_total") and not \
            last_snapshot.get("handoff_fallbacks_total"):
        return {}
    out: dict = {}
    for key in ("handoffs_total", "pages_shipped_total",
                "page_ship_bytes_total", "handoff_fallbacks_total",
                "replicas_prefill_healthy", "replicas_decode_healthy",
                "handoff_p50_s", "handoff_p99_s"):
        if key in last_snapshot:
            out[key] = last_snapshot[key]
    handoffs = out.get("handoffs_total", 0) or 0
    attempts = handoffs + (out.get("handoff_fallbacks_total", 0) or 0)
    if attempts:
        out["handoff_success_frac"] = round(handoffs / attempts, 4)
    if (first_t is not None and last_t is not None and last_t > first_t
            and out.get("page_ship_bytes_total")):
        out["transfer_bytes_per_s"] = round(
            out["page_ship_bytes_total"] / (last_t - first_t), 1)
    return out


def analyze_autoscale(path) -> dict:
    """Autoscaling section (ISSUE 19) from the router's
    ``router.jsonl``: scale_up/scale_down/role_flip events folded
    with the last snapshot's autoscale counters and gauges —
    replica-seconds burned, the final target/actual split, and the
    membership envelope the policy walked (peak/floor of the actual
    replica gauge across snapshots). Empty when the autoscaler never
    ran — the section only renders for fleets that scaled."""
    counts: dict = {}
    last_snapshot: dict = {}
    peak = floor = None
    for rec in load_jsonl(path):
        ev = rec.get("event")
        counts[ev] = counts.get(ev, 0) + 1
        if ev == "snapshot":
            last_snapshot = rec
            n = rec.get("autoscale_actual_replicas")
            if isinstance(n, (int, float)):
                peak = n if peak is None else max(peak, n)
                floor = n if floor is None else min(floor, n)
    ran = (counts.get("scale_up", 0) or counts.get("scale_down", 0)
           or counts.get("role_flip", 0)
           or "autoscale_actual_replicas" in last_snapshot)
    if not ran:
        return {}
    out: dict = {
        "scale_ups": counts.get("scale_up", 0),
        "scale_downs": counts.get("scale_down", 0),
        "role_flips": counts.get("role_flip", 0),
        "replicas_added": counts.get("add_replica", 0),
        "replicas_removed": counts.get("remove_replica", 0),
        "peak_replicas": peak,
        "floor_replicas": floor,
    }
    for key in ("autoscale_scale_up_total",
                "autoscale_scale_down_total",
                "autoscale_role_flip_total", "replica_seconds_total",
                "autoscale_target_replicas",
                "autoscale_actual_replicas",
                "autoscale_healthy_replicas", "autoscale_pressure",
                "autoscale_predicted_pressure",
                "autoscale_arrival_rate"):
        if key in last_snapshot:
            out[key] = last_snapshot[key]
    return {k: v for k, v in out.items() if v is not None}


def analyze_kvtier(records: list, fleet_path=None) -> dict:
    """KV tiers (serving) section (ISSUE 13). Engine side, from the
    slot engine's per-chunk ``serve_chunk`` records: demote/promote
    traffic (cumulative — last record wins), checksum failures,
    destroy-on-evict degradations, and the per-tier occupancy high
    water. Fleet side, from the router's ``router.jsonl`` counter
    snapshots: miss-driven peer page pulls (volume + p50/p99 latency)
    and restart re-warm events. Empty when neither the tier nor peer
    migration ever engaged — the section renders only when the
    feature ran."""
    out: dict = {}
    serve = [r for r in records or ()
             if r.get("event") == "serve_chunk"
             and r.get("tier_demoted_blocks_total") is not None]
    if serve:
        last = serve[-1]
        for k in ("tier_demoted_blocks_total",
                  "tier_promoted_blocks_total",
                  "tier_demote_bytes_total", "tier_promote_bytes_total",
                  "tier_checksum_failures_total",
                  "tier_exhaust_drops_total",
                  "tier_host_blocks", "tier_disk_blocks"):
            if last.get(k) is not None:
                out[k] = last[k]
        host_hw = [r["tier_host_bytes"] for r in serve
                   if r.get("tier_host_bytes") is not None]
        if host_hw:
            out["tier_host_bytes_max"] = max(host_hw)
    if fleet_path is not None:
        last_snapshot: dict = {}
        for rec in load_jsonl(fleet_path):
            if rec.get("event") == "snapshot":
                last_snapshot = rec
        for k in ("peer_pulls_total", "peer_pull_blocks_total",
                  "peer_pull_bytes_total", "peer_pull_failures_total",
                  "peer_pull_timeouts_total", "peer_pull_p50_s",
                  "peer_pull_p99_s", "rewarm_events_total",
                  "rewarm_pulls_total", "rewarm_blocks_total",
                  "rewarm_failures_total"):
            v = last_snapshot.get(k)
            if v:
                out[k] = v
    return out


def analyze_timeseries(path, last_n: int = 600) -> dict:
    """Fleet timeline section (ISSUE 14) from a ``timeseries.jsonl``
    (observability/timeseries.py): per-series p50/p99/max over the
    trailing window — the trend picture a single /metrics snapshot
    cannot give. Empty when the file holds no points."""
    from pytorch_distributed_template_tpu.observability.timeseries \
        import load_timeseries
    from pytorch_distributed_template_tpu.utils.promtext import (
        percentile,
    )

    points = load_timeseries(path)[-last_n:]
    if not points:
        return {}
    out: dict = {"points": len(points)}
    names = sorted({k for p in points for k in p
                    if k not in ("t", "span_s")})
    for name in names:
        vals = sorted(p[name] for p in points if name in p)
        if not vals:
            continue
        out[f"{name}_p50"] = round(percentile(vals, 0.5), 4)
        out[f"{name}_p99"] = round(percentile(vals, 0.99), 4)
        out[f"{name}_max"] = round(vals[-1], 4)
    return out


def analyze_reqtrace(run_dir=None, span_files=None) -> dict:
    """Request-scoped tracing section (ISSUE 8): stitch every
    ``spans.jsonl`` under the run dir (router + replicas) into
    cross-process request timelines and fold the tail-latency
    attribution into a flat table — stitched/partial counts, segment
    p50/p99s, coverage (attributed fraction of e2e, residual NOT
    hidden), and how many bounded slow-request SLO dumps the run left
    behind. ``scripts/trace_stitch.py`` renders the full per-request
    tables and the Perfetto trace from the same machinery."""
    from pytorch_distributed_template_tpu.observability import reqtrace

    files = reqtrace.resolve_span_files(span_files, run_dir)
    if not files:
        return {}
    spans = reqtrace.load_spans(files)
    if not spans:
        return {}
    report = reqtrace.stitch_spans(spans)
    att = reqtrace.attribution(report)
    out: dict = {
        "span_files": len(files),
        "requests": report["counts"]["requests"],
        "stitched": report["counts"]["stitched"],
        "partial": report["counts"]["partial"],
    }
    for k, v in att.items():
        if isinstance(v, (int, float)):
            out[k] = v
    if run_dir is not None:
        out["slow_request_dumps"] = len(
            list(Path(run_dir).rglob("slow_request_*.json")))
    return out


def analyze_anomalies(run_dir) -> dict:
    """Summarize the ``anomaly_*.json`` forensic bundles in a run dir."""
    files = sorted(Path(run_dir).glob("anomaly_*.json"))
    dumps = []
    for f in files:
        try:
            a = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        dumps.append({
            "file": f.name,
            "step": a.get("step"),
            "reasons": [r.get("kind") for r in a.get("reasons", [])],
        })
    return {"dump_count": len(dumps), "dumps": dumps}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------


def to_markdown(report: dict) -> str:
    lines = ["# Telemetry report", ""]

    def table(title, d: dict):
        if not d:
            return
        lines.append(f"## {title}")
        lines.append("")
        lines.append("| metric | value |")
        lines.append("|---|---|")
        for k, v in d.items():
            if isinstance(v, (list, dict)):
                continue
            lines.append(f"| {k} | {v} |")
        lines.append("")

    table("Flight recorder", report.get("telemetry", {}))
    table("Set-up by phase (seconds)",
          report.get("telemetry", {}).get("setup"))
    table("Stalled iterations",
          report.get("telemetry", {}).get("stalls"))
    table("Prefix cache (serving)", report.get("prefix_cache", {}))
    table("Tensor parallel (serving)", report.get("tensor_parallel", {}))
    table("Supervisor", report.get("supervisor", {}))
    table("Fleet (router)", report.get("fleet", {}))
    table("Disaggregation (serving)", report.get("disagg", {}))
    table("Autoscaling", report.get("autoscale", {}))
    table("KV tiers (serving)", report.get("kvtier", {}))
    table("Fleet timeline (time series)",
          report.get("timeseries", {}))
    table("Request tracing (p99 attribution)",
          report.get("reqtrace", {}))
    drift = report.get("drift") or {}
    if drift:
        lines.append("## Service-model drift gate")
        lines.append("")
        lines.append("| segment | quantile | current | baseline | "
                     "rel shift | verdict |")
        lines.append("|---|---|---|---|---|---|")
        shifted = {(s.get("segment"), s.get("quantile"))
                   for s in drift.get("shifts", [])}
        for row in drift.get("compared", []):
            verdict = ("**SHIFT**" if (row["segment"],
                                       row["quantile"]) in shifted
                       else "ok")
            lines.append(
                f"| {row['segment']} | {row['quantile']} | "
                f"{row['current']} | {row['baseline']} | "
                f"{row['rel_shift']} | {verdict} |")
        for s in drift.get("shifts", []):
            if s.get("kind") != "shift":
                lines.append(f"- **SHIFT** ({s.get('kind')}): {s}")
        lines.append("")
    tr = report.get("trace") or {}
    if tr.get("top_spans"):
        lines.append("## Host spans (top by total time)")
        lines.append("")
        lines.append("| span | total ms | count |")
        lines.append("|---|---|---|")
        for s in tr["top_spans"]:
            lines.append(
                f"| {s['name']} | {s['total_ms']} | {s['count']} |"
            )
        lines.append("")
    an = report.get("anomalies") or {}
    if an.get("dump_count"):
        lines.append("## Anomaly dumps")
        lines.append("")
        for d in an["dumps"]:
            lines.append(
                f"- `{d['file']}` step {d['step']}: "
                f"{', '.join(d['reasons'])}"
            )
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="offline telemetry analyzer + drift gate"
    )
    p.add_argument("--run-dir", type=str, default=None,
                   help="run directory: picks up telemetry.jsonl, "
                        "trace.json and anomaly_*.json automatically")
    p.add_argument("--telemetry", type=str, default=None,
                   help="explicit telemetry.jsonl path")
    p.add_argument("--trace", type=str, default=None,
                   help="explicit trace.json path")
    p.add_argument("--supervisor", type=str, default=None,
                   help="explicit supervisor.jsonl path (the "
                        "resilience supervisor's lifecycle log; "
                        "--run-dir also auto-discovers one)")
    p.add_argument("--fleet", type=str, default=None,
                   help="explicit router.jsonl path (the serving "
                        "fleet front door's lifecycle log, "
                        "scripts/serve_fleet.py --run-dir; --run-dir "
                        "here also auto-discovers one)")
    p.add_argument("--spans", type=str, nargs="*", default=None,
                   help="explicit spans.jsonl paths for the "
                        "request-tracing section (--run-dir also "
                        "auto-discovers every spans.jsonl under it; "
                        "scripts/trace_stitch.py renders the full "
                        "per-request tables + Perfetto trace)")
    p.add_argument("--drift", type=str, nargs=2, default=None,
                   metavar=("CURRENT", "BASELINE"),
                   help="distribution-level regression gate (ISSUE "
                        "14): compare two service_model.json files "
                        "per segment (p50/p99, both directions); "
                        "exit 1 on any shift past --drift-tolerance")
    p.add_argument("--drift-tolerance", type=float, default=0.25,
                   help="allowed RELATIVE per-quantile shift between "
                        "the two service models (0 = exact match "
                        "required; a self-compare passes at 0)")
    p.add_argument("--timeseries", type=str, default=None,
                   help="explicit timeseries.jsonl path (--run-dir "
                        "also auto-discovers one)")
    p.add_argument("--json", action="store_true",
                   help="emit the report as JSON instead of markdown")
    p.add_argument("--out", type=str, default=None,
                   help="also write the report to this path")
    args = p.parse_args(argv)

    report: dict = {}
    try:
        records: list = []
        tel_path = args.telemetry
        run_dir = Path(args.run_dir) if args.run_dir else None
        if tel_path is None and run_dir is not None:
            cand = run_dir / "telemetry.jsonl"
            tel_path = cand if cand.exists() else None
        if tel_path is not None:
            records = load_jsonl(tel_path)
            report["telemetry"] = analyze_telemetry(records)
            prefix = analyze_prefix(records)
            if prefix:
                report["prefix_cache"] = prefix
            tp = analyze_tp(records)
            if tp:
                report["tensor_parallel"] = tp
        trace_path = args.trace
        if trace_path is None and run_dir is not None:
            cand = run_dir / "trace.json"
            trace_path = cand if cand.exists() else None
        if trace_path is not None:
            report["trace"] = analyze_trace(trace_path)
        sup_path = args.supervisor
        if sup_path is None and run_dir is not None:
            cand = run_dir / "supervisor.jsonl"
            sup_path = cand if cand.exists() else None
        if sup_path is not None:
            report["supervisor"] = analyze_supervisor(sup_path)
        fleet_path = args.fleet
        if fleet_path is None and run_dir is not None:
            cand = run_dir / "router.jsonl"
            fleet_path = cand if cand.exists() else None
        if fleet_path is not None:
            report["fleet"] = analyze_fleet(fleet_path)
            disagg = analyze_disagg(fleet_path)
            if disagg:
                report["disagg"] = disagg
            autoscale = analyze_autoscale(fleet_path)
            if autoscale:
                report["autoscale"] = autoscale
        kvtier = analyze_kvtier(records, fleet_path=fleet_path)
        if kvtier:
            report["kvtier"] = kvtier
        ts_path = args.timeseries
        if ts_path is None and run_dir is not None:
            # a fleet run leaves one at the top (the poller's) and
            # one per replica save dir — the top-level one is the
            # fleet view; explicit --timeseries picks any other
            cand = run_dir / "timeseries.jsonl"
            ts_path = cand if cand.exists() else None
        if ts_path is not None:
            ts = analyze_timeseries(ts_path)
            if ts:
                report["timeseries"] = ts
        if args.spans or run_dir is not None:
            rt = analyze_reqtrace(run_dir=run_dir,
                                  span_files=args.spans)
            if rt:
                report["reqtrace"] = rt
        if run_dir is not None:
            report["anomalies"] = analyze_anomalies(run_dir)
    except (OSError, ValueError) as e:
        print(f"telemetry_report: {e}", file=sys.stderr)
        return 2
    if not report and args.drift is None:
        p.print_usage(sys.stderr)
        print("telemetry_report: nothing to analyze (pass --run-dir, "
              "--telemetry and/or --drift)", file=sys.stderr)
        return 2

    rc = 0
    if args.drift is not None:
        from pytorch_distributed_template_tpu.observability.servicedist \
            import drift_report, load_service_model

        try:
            cur = load_service_model(args.drift[0])
            base = load_service_model(args.drift[1])
        except (OSError, ValueError) as e:
            print(f"telemetry_report: --drift: {e}", file=sys.stderr)
            return 2
        result = drift_report(cur, base,
                              tolerance=args.drift_tolerance)
        report["drift"] = result
        if result["shifts"]:
            rc = 1
            for s in result["shifts"]:
                print(f"DRIFT: {json.dumps(s)}", file=sys.stderr)
    rendered = (json.dumps(report, indent=2) if args.json
                else to_markdown(report))
    print(rendered)
    if args.out:
        try:
            Path(args.out).write_text(rendered + "\n")
        except OSError as e:
            print(f"telemetry_report: --out: {e}", file=sys.stderr)
            return 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
