"""``GET /dashboard``: the router's self-contained operator page.

One stdlib-rendered HTML document — no framework, no CDN, no
JavaScript beyond a meta refresh — answering the questions an
operator otherwise greps four JSONL files for:

- per-replica state / role / brownout level / queue depth / inflight
  (the manager's live snapshot);
- the fleet counter board: routing split, shed/deadline/hedge
  counters, tier demote/promote traffic, peer-pull + re-warm
  counters, goodput vs raw tokens;
- **sparklines** over the poller-fed time-series store
  (observability/timeseries.py): queue depth, tokens/s, goodput/s,
  brownout level — the trend ``/metrics`` cannot show;
- the **p99 attribution table** from the run's stitched spans (the
  same machinery as ``scripts/trace_stitch.py``, bounded so a huge
  span archive cannot wedge a dashboard request).

Everything renders from data already in memory or already on disk;
a dashboard request never touches a replica.
"""
from __future__ import annotations

import html
import threading
import time
from typing import List, Optional, Tuple

#: refuse to stitch span archives past this (the dashboard is a live
#: page, not an offline analyzer; trace_stitch.py owns the big runs)
MAX_SPAN_BYTES = 16 << 20

# attribution cache keyed on the span files' (path, mtime, size)
# signature: an auto-refreshing tab must not re-parse megabytes of
# JSONL on the router's handler threads every 5 s for an unchanged
# archive
_att_lock = threading.Lock()
_att_cache: dict = {"sig": None, "value": None}

_CSS = """
body{font-family:system-ui,sans-serif;margin:1.2em;background:#fafafa;
     color:#222}
h1{font-size:1.3em}h2{font-size:1.05em;margin-top:1.4em}
table{border-collapse:collapse;margin:.4em 0}
td,th{border:1px solid #ccc;padding:.25em .6em;font-size:.85em;
      text-align:left}
th{background:#eee}
.state-healthy{color:#0a7a26;font-weight:600}
.state-ejected{color:#b00020;font-weight:600}
.state-draining,.state-starting{color:#8a6d00;font-weight:600}
.spark{display:inline-block;vertical-align:middle;margin-left:.5em}
.sparkrow{font-size:.85em;margin:.15em 0}
.muted{color:#777;font-size:.8em}
"""


def sparkline(values: List[float], width: int = 180,
              height: int = 28) -> str:
    """Inline SVG polyline over a value series (empty series -> a
    flat muted line). Self-contained: no external assets."""
    if not values:
        values = [0.0]
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    n = max(len(values) - 1, 1)
    pts = " ".join(
        f"{round(i * width / n, 1)},"
        f"{round(height - 2 - (v - lo) / span * (height - 4), 1)}"
        for i, v in enumerate(values))
    return (f'<svg class="spark" width="{width}" height="{height}" '
            f'viewBox="0 0 {width} {height}">'
            f'<polyline fill="none" stroke="#1565c0" '
            f'stroke-width="1.5" points="{pts}"/></svg>')


def _table(rows: List[Tuple], header: Tuple) -> List[str]:
    out = ["<table>", "<tr>" + "".join(
        f"<th>{html.escape(str(h))}</th>" for h in header) + "</tr>"]
    for row in rows:
        out.append("<tr>" + "".join(
            str(c) if str(c).startswith("<td") else
            f"<td>{html.escape(str(c))}</td>" for c in row) + "</tr>")
    out.append("</table>")
    return out


#: sparkline picks, preferred-first (only series actually present
#: render); anything else present fills remaining slots up to the cap
PREFERRED_SERIES = (
    "fleet_tokens_generated_per_s", "fleet_requests_per_s",
    "goodput_tokens_per_s", "queue_depth", "waiting",
    "proxy_inflight", "replicas_healthy", "fleet_brownout_level",
    "shed_per_s", "fleet_slo_breach_per_s",
)
MAX_SPARKS = 12


def _counter_rows(metrics: dict, keys) -> List[Tuple[str, object]]:
    return [(k, metrics[k]) for k in keys if metrics.get(k)
            not in (None, 0, 0.0)]


def render_dashboard(manager, admission, stats, slo=None,
                     tsdb=None, run_dir=None) -> str:
    """The full page. Every section degrades independently: no
    store -> no sparklines, no spans -> no attribution table."""
    snap = manager.snapshot()
    counters = manager.snapshot_counters()
    parts: List[str] = [
        "<!DOCTYPE html>", "<html>", "<head>",
        '<meta charset="utf-8">',
        '<meta http-equiv="refresh" content="5">',
        "<title>fleet dashboard</title>",
        f"<style>{_CSS}</style>", "</head>", "<body>",
        f"<h1>Fleet dashboard <span class=muted>policy="
        f"{html.escape(str(snap['policy']))} · status="
        f"{html.escape(str(snap['status']))} · "
        f"{time.strftime('%H:%M:%S')}</span></h1>",
    ]

    # -- replicas ----------------------------------------------------------
    parts.append("<h2>Replicas</h2>")
    rows = []
    for r in snap["replicas"]:
        state = str(r["state"])
        rep = manager.replicas.get(r["id"])
        brown = int((rep.polled.get("brownout_level", 0) or 0)
                    if rep is not None else 0)
        rows.append((
            r["id"],
            f'<td><span class="state-{html.escape(state)}">'
            f"{html.escape(state)}</span></td>",
            r.get("role", "both"), brown, r["queue_depth"],
            r["inflight"], r["slots"], r["requests_total"],
            r["prefix_hit_tokens_total"], r.get("url") or "-",
        ))
    parts += _table(rows, ("id", "state", "role", "brownout",
                           "queue", "inflight", "slots", "requests",
                           "prefix hit tok", "url"))

    # -- queues + goodput --------------------------------------------------
    parts.append("<h2>Admission + goodput</h2>")
    depth = admission.depths() if admission is not None else {}
    rows = [(k, v) for k, v in sorted(depth.items())]
    goodput = getattr(stats, "goodput", None)
    if goodput is not None:
        gp = goodput.stats()
        rows += [(k, gp[k]) for k in
                 ("raw_tokens_total", "served_tokens_total",
                  "goodput_tokens_total", "goodput_frac",
                  "goodput_tok_s", "raw_tok_s") if k in gp]
    if slo is not None:
        rows += sorted(slo.stats().items())
    parts += _table(rows, ("metric", "value"))

    # -- fleet counters ----------------------------------------------------
    parts.append("<h2>Fleet counters</h2>")
    rows = _counter_rows(counters, (
        "fleet_requests_total", "fleet_tokens_generated_total",
        "fleet_prefix_hit_tokens_total", "routed_prefix_total",
        "routed_least_loaded_total", "routed_round_robin_total",
        "dispatch_errors_total", "ejections_total",
        "readmissions_total", "wedged_ejections_total",
        "handoffs_total", "pages_shipped_total",
        "page_ship_bytes_total",
        # tier / peer-migration board (ISSUE 13 counters)
        "peer_pulls_total", "peer_pull_blocks_total",
        "peer_pull_bytes_total", "peer_pull_failures_total",
        "peer_pull_timeouts_total", "rewarm_events_total",
        "rewarm_pulls_total", "rewarm_blocks_total",
        "fleet_brownout_level", "last_recovery_s",
    ))
    parts += _table(rows or [("(no traffic yet)", "-")],
                    ("counter", "value"))

    # -- autoscaling (ISSUE 19) --------------------------------------------
    # gauges ride the manager counter snapshot via extra_counters_fn;
    # a fleet without a running autoscaler renders one muted line
    parts.append("<h2>Autoscaling</h2>")
    if "autoscale_actual_replicas" in counters:
        parts.append(
            f'<p class="muted">target='
            f'{counters.get("autoscale_target_replicas")} · actual='
            f'{counters.get("autoscale_actual_replicas")} · healthy='
            f'{counters.get("autoscale_healthy_replicas")} · '
            f'pressure={counters.get("autoscale_pressure")} '
            f'(predicted='
            f'{counters.get("autoscale_predicted_pressure")}) · '
            f'arrival_rate='
            f'{counters.get("autoscale_arrival_rate")}/s</p>')
        rows = _counter_rows(counters, (
            "autoscale_scale_up_total", "autoscale_scale_down_total",
            "autoscale_role_flip_total", "replica_seconds_total",
        ))
        parts += _table(rows or [("(no scale events yet)", "-")],
                        ("counter", "value"))
    else:
        parts.append('<p class="muted">autoscaler off '
                     '(serve_fleet --autoscale on)</p>')

    # -- token integrity (ISSUE 18) ----------------------------------------
    # fleet-level shadow-audit verdict + per-replica coverage split by
    # serve-path fingerprint, read from the poller's stored /metrics
    # bodies (rep.polled) — a dashboard request never touches a replica
    parts.append("<h2>Token integrity (shadow audit)</h2>")
    audited = int(counters.get("fleet_audit_sampled_total", 0) or 0)
    diverged = int(
        counters.get("fleet_token_divergence_total", 0) or 0)
    dropped = int(counters.get("fleet_audit_dropped_total", 0) or 0)
    verdict = ("no auditing replicas"
               if not audited and not diverged
               else "DIVERGENT" if diverged else "clean")
    parts.append(
        f'<p class="muted">verdict: {html.escape(verdict)} · audited '
        f"{audited} · divergent {diverged} · dropped {dropped}</p>")
    cov_rows = []
    for r in snap["replicas"]:
        rep = manager.replicas.get(r["id"])
        polled = (rep.polled or {}) if rep is not None else {}
        for k in sorted(polled):
            if not (k.startswith("audit_path_")
                    and k.endswith("_audited_total")):
                continue
            fp = k[len("audit_path_"):-len("_audited_total")]
            cov_rows.append((
                r["id"], fp,
                int(polled.get(f"serve_path_{fp}_total", 0) or 0),
                int(polled.get(k, 0) or 0),
                int(polled.get(f"audit_path_{fp}_divergent_total", 0)
                    or 0)))
    if cov_rows:
        parts += _table(cov_rows, ("replica", "fingerprint", "served",
                                   "audited", "divergent"))

    # -- sparklines --------------------------------------------------------
    parts.append("<h2>Timeline (poller window)</h2>")
    if tsdb is None or not tsdb.points():
        parts.append('<p class="muted">no time-series store attached '
                     "(or no points yet)</p>")
    else:
        names = [n for n in PREFERRED_SERIES
                 if tsdb.series(n)]
        for n in tsdb.series_names():
            if len(names) >= MAX_SPARKS:
                break
            if n not in names:
                names.append(n)
        for name in names[:MAX_SPARKS]:
            vals = [v for _, v in tsdb.series(name)]
            last = vals[-1] if vals else 0
            parts.append(
                f'<div class="sparkrow">{html.escape(name)} '
                f"= {round(last, 3)}{sparkline(vals)}</div>")

    # -- p99 attribution ---------------------------------------------------
    parts.append("<h2>p99 attribution (stitched spans)</h2>")
    att = _attribution(run_dir)
    if not att:
        parts.append('<p class="muted">no stitched spans under the '
                     "run dir (yet)</p>")
    else:
        seg_rows = [(n, att.get(f"seg_{n}_p50_s"),
                     att.get(f"seg_{n}_p99_s"))
                    for n in sorted(
                        k[len("seg_"):-len("_p50_s")] for k in att
                        if k.startswith("seg_")
                        and k.endswith("_p50_s"))]
        seg_rows.append(("e2e", att.get("e2e_p50_s"),
                         att.get("e2e_p99_s")))
        parts += _table(seg_rows, ("segment", "p50 s", "p99 s"))
        worst = att.get("p99_request") or {}
        if worst:
            parts.append(
                f'<p class="muted">p99 request '
                f"{html.escape(str(worst.get('rid')))}: "
                f"e2e {worst.get('e2e_s')} s — "
                + ", ".join(
                    f"{html.escape(k)}={v:.4f}s" for k, v in sorted(
                        (worst.get("segments") or {}).items(),
                        key=lambda kv: -kv[1])[:6]) + "</p>")
    parts += ["</body>", "</html>"]
    return "\n".join(parts)


def _attribution(run_dir) -> Optional[dict]:
    """Bounded stitch of the run dir's span files (None when absent
    or oversized — the page must stay cheap)."""
    if run_dir is None:
        return None
    from ..observability import reqtrace

    files = reqtrace.discover_span_files(run_dir)
    if not files:
        return None
    try:
        stat = [(str(f), s.st_mtime, s.st_size)
                for f, s in ((f, f.stat()) for f in files)]
        if sum(s[2] for s in stat) > MAX_SPAN_BYTES:
            return None
    except OSError:
        return None
    sig = tuple(stat)
    with _att_lock:
        if _att_cache["sig"] == sig:
            return _att_cache["value"]
    spans = reqtrace.load_spans(files)
    att = None
    if spans:
        att = reqtrace.attribution(reqtrace.stitch_spans(spans))
        if not att.get("attributed_requests"):
            att = None
    with _att_lock:
        _att_cache["sig"] = sig
        _att_cache["value"] = att
    return att
