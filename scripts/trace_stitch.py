#!/usr/bin/env python
"""Cross-process request-trace stitcher (ISSUE 8).

Merges the per-process ``spans.jsonl`` files a fleet run leaves behind
(the router's at the top of ``--run-dir``, each replica's under its
save dir — serve.py and fleet/router.py append them via
observability/reqtrace.RequestTracer) into:

- one **Perfetto/Chrome-loadable trace** (``--perfetto OUT.json``):
  every process on its own row, spans keyed by request id, flow events
  linking the router's proxy span to the replica's handler span — open
  it and follow a single request across the fleet;
- a **per-request timeline table**: each request's non-overlapping
  latency segments (router queue / WFQ admission wait / proxy hop /
  replica queue / admit-to-first-token / decode / stream) with the
  residual REPORTED, not hidden;
- a **tail-latency attribution** section: per-segment p50/p99 plus
  the p99 request's own decomposition — "p99 is 300 ms" becomes
  "240 ms of it is WFQ wait".

Clock skew between files is aligned causally (a replica span cannot
start before the router dispatched the request; skewed processes are
shifted by the median violation). ``--client SUMMARY.json`` joins a
loadgen summary (fleet/loadgen.py ``by_request``) so attribution runs
against CLIENT-measured e2e.

    python scripts/trace_stitch.py --run-dir fleet_run \\
        --perfetto merged_trace.json
    python scripts/trace_stitch.py --run-dir fleet_run --json \\
        --client loadgen_summary.json

CI gates: ``--require-stitched N`` (at least N fully cross-process
request timelines) and ``--min-coverage F`` (median attributed
fraction of e2e) exit nonzero when violated.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from pytorch_distributed_template_tpu.observability import (  # noqa: E402
    reqtrace,
)


def load_client_e2e(path) -> dict:
    """``{rid: total_s}`` from a loadgen summary (or replay) JSON."""
    data = json.loads(Path(path).read_text())
    rows = data.get("by_request") or data.get("results") or []
    return {r["rid"]: float(r["total_s"]) for r in rows
            if r.get("rid") and r.get("total_s") is not None
            and r.get("ok")}


def to_markdown(report: dict, top: int = 12) -> str:
    counts = report["counts"]
    att = report.get("attribution") or {}
    lines = ["# Stitched request trace", ""]
    lines.append(f"- span files merged over {counts['requests']} "
                 f"request id(s): **{counts['stitched']} stitched** "
                 f"(cross-process), {counts['partial']} partial "
                 "(single-process / orphan spans)")
    if report.get("offsets"):
        lines.append(f"- clock offsets applied: {report['offsets']}")
    lines.append("")
    if att:
        lines.append("## Tail-latency attribution (stitched requests)")
        lines.append("")
        lines.append("| segment | p50 s | p99 s |")
        lines.append("|---|---|---|")
        names = sorted({k[len("seg_"):-len("_p50_s")]
                        for k in att if k.startswith("seg_")
                        and k.endswith("_p50_s")})
        for n in names:
            lines.append(f"| {n} | {att.get(f'seg_{n}_p50_s')} "
                         f"| {att.get(f'seg_{n}_p99_s')} |")
        lines.append(f"| **e2e** | {att.get('e2e_p50_s')} "
                     f"| {att.get('e2e_p99_s')} |")
        if att.get("residual_p99_s") is not None:
            lines.append(f"| residual | - "
                         f"| {att.get('residual_p99_s')} |")
        lines.append("")
        if att.get("coverage_p50") is not None:
            lines.append(f"- attributed coverage: p50 "
                         f"{att['coverage_p50']}, min "
                         f"{att['coverage_min']}")
        worst = att.get("p99_request")
        if worst:
            lines.append(f"- p99 request `{worst['rid']}` "
                         f"(e2e {worst['e2e_s']} s): "
                         + ", ".join(
                             f"{k}={v:.4f}s" for k, v in
                             sorted(worst["segments"].items(),
                                    key=lambda kv: -kv[1]))
                         + (f", residual={worst['residual_s']}s"
                            if worst.get("residual_s") is not None
                            else ""))
        lines.append("")
    rows = [r for r in report["requests"] if r["stitched"]]
    rows.sort(key=lambda r: -(r.get("e2e_s") or 0))
    if rows:
        lines.append(f"## Slowest stitched requests (top {top})")
        lines.append("")
        lines.append("| rid | e2e s | ttft s | tokens | "
                     "dominant segment | residual s |")
        lines.append("|---|---|---|---|---|---|")
        for r in rows[:top]:
            dom = (max(r["segments"].items(),
                       key=lambda kv: kv[1])
                   if r["segments"] else ("-", 0.0))
            lines.append(
                f"| {r['rid']} | {r.get('e2e_s')} "
                f"| {r.get('ttft_s', '-')} | {r.get('tokens', '-')} "
                f"| {dom[0]} ({dom[1]:.4f}s) "
                f"| {r.get('residual_s', '-')} |")
        lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="merge per-process spans.jsonl files into one "
                    "cross-process request trace + attribution")
    p.add_argument("--run-dir", default=None,
                   help="fleet run dir: every spans.jsonl under it "
                        "(recursive) is merged")
    p.add_argument("--spans", nargs="*", default=None,
                   help="explicit spans.jsonl paths (instead of / in "
                        "addition to --run-dir discovery)")
    p.add_argument("--client", default=None,
                   help="loadgen summary JSON (by_request) to join "
                        "client-measured e2e per rid")
    p.add_argument("--perfetto", default=None, metavar="OUT.json",
                   help="write the merged Chrome/Perfetto trace "
                        "(flow events link processes per request)")
    p.add_argument("--service-model", default=None,
                   metavar="OUT.json",
                   help="export the versioned per-segment "
                        "service-time model (ISSUE 14, "
                        "observability/servicedist.py) — log-spaced "
                        "histograms + quantiles per (segment x route "
                        "class), the simulator's input contract; "
                        "telemetry_report --drift gates two of these")
    p.add_argument("--json", action="store_true",
                   help="emit the stitch report as JSON (default: "
                        "markdown tables)")
    p.add_argument("--out", default=None,
                   help="also write the report to this path")
    p.add_argument("--require-stitched", type=int, default=0,
                   metavar="N",
                   help="exit 1 unless >= N fully cross-process "
                        "request timelines stitched (CI gate)")
    p.add_argument("--min-coverage", type=float, default=0.0,
                   metavar="FRAC",
                   help="exit 1 when the median attributed fraction "
                        "of e2e falls below this (CI gate; only "
                        "checked when requests stitched)")
    args = p.parse_args(argv)

    files = [str(f) for f in reqtrace.resolve_span_files(
        args.spans, args.run_dir)]
    if not files:
        print("trace_stitch: no spans.jsonl found (pass --run-dir "
              "or --spans)", file=sys.stderr)
        return 2
    spans = reqtrace.load_spans(files)
    client = None
    if args.client:
        try:
            client = load_client_e2e(args.client)
        except (OSError, ValueError, KeyError) as e:
            print(f"trace_stitch: --client: {e}", file=sys.stderr)
            return 2
    report = reqtrace.stitch_spans(spans, client_e2e_by_rid=client)
    report["attribution"] = reqtrace.attribution(report)
    report["span_files"] = files

    if args.perfetto:
        trace = reqtrace.to_perfetto(spans)
        try:
            Path(args.perfetto).parent.mkdir(parents=True,
                                             exist_ok=True)
            Path(args.perfetto).write_text(json.dumps(trace))
        except OSError as e:
            print(f"trace_stitch: --perfetto: {e}", file=sys.stderr)
            return 2

    if args.service_model:
        from pytorch_distributed_template_tpu.observability import (
            servicedist,
        )

        model = servicedist.build_service_model(
            spans, client_e2e_by_rid=client)
        try:
            servicedist.write_service_model(model,
                                            args.service_model)
        except OSError as e:
            print(f"trace_stitch: --service-model: {e}",
                  file=sys.stderr)
            return 2
        print(f"service model: {len(model['segments'])} segment(s), "
              f"coverage {model['coverage']['frac']} over "
              f"{model['counts']['modeled']} request(s) -> "
              f"{args.service_model}", file=sys.stderr)

    rendered = (json.dumps(report, indent=2) if args.json
                else to_markdown(report))
    print(rendered)
    if args.out:
        try:
            Path(args.out).write_text(rendered + "\n")
        except OSError as e:
            print(f"trace_stitch: --out: {e}", file=sys.stderr)
            return 2

    rc = 0
    stitched = report["counts"]["stitched"]
    if args.require_stitched and stitched < args.require_stitched:
        print(f"trace_stitch: GATE: only {stitched} stitched "
              f"cross-process request(s) < required "
              f"{args.require_stitched}", file=sys.stderr)
        rc = 1
    cov = (report.get("attribution") or {}).get("coverage_p50")
    if (args.min_coverage and stitched
            and cov is not None and cov < args.min_coverage):
        print(f"trace_stitch: GATE: median attributed coverage "
              f"{cov} < {args.min_coverage} (residual too large)",
              file=sys.stderr)
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
