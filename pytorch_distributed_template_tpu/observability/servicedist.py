"""Per-stage service-time models + goodput accounting (ISSUE 14).

The stitched request timelines (observability/reqtrace.py) decompose
every request into non-overlapping segments, but PR 8 only ever
*summarized* them (p50/p99 per segment). The discrete-event simulator
and any scale-up policy (ROADMAP item 5) need the actual measured
**distributions** — Splitwise and DistServe both built their
phase-split and provisioning decisions on exactly this input. This
module extracts them and freezes the result as a **versioned
``service_model.json``**, the simulator's input contract:

- per segment (admit / decode / scheduler_queue / ...), a log-spaced
  histogram over SHARED global bin edges (two models compare
  bin-to-bin) plus exact p50/p90/p99 from the raw samples (via THE
  package percentile convention, utils/promtext.percentile);
- the same, split per **route class** — ``(admit mode: warm / cold /
  paged) × (stream / unary) × (prompt-length bucket)`` — because a
  warm pointer-update admit and a cold 512-token prefill are
  different random variables and a simulator that pools them
  reproduces neither;
- **coverage**: the attributed fraction of stitched request wall
  time, so a consumer knows how much latency the model explains (the
  CI gate holds it ≥ 0.9).

:func:`drift_report` compares two models per-segment with a relative
tolerance — the distribution-level regression gate behind
``telemetry_report --drift`` (a p99 shift in ``admit`` fails CI even
when aggregate tok/s held).

:class:`GoodputMeter` is the fleet-wide goodput ledger: raw tokens vs
SERVED tokens (error / cancelled / deadline-truncated tokens
excluded) vs SLO-compliant tokens, with per-tenant shares — the
"useful work per second" number an autoscaler optimizes, scraped on
the router's ``/metrics``.

Stdlib-only: the fleet router imports this and must stay jax-free.
"""
from __future__ import annotations

import bisect
import json
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from ..utils.promtext import percentile as _pctl
from . import reqtrace

SERVICE_MODEL_VERSION = 1
SERVICE_MODEL_FILENAME = "service_model.json"

#: shared log-spaced bin edges (seconds): 100 µs .. 1000 s, 8 bins per
#: decade. Global and versioned WITH the model so histograms from two
#: runs align bin-to-bin — drift comparison and simulator sampling
#: never need to rebin.
LOG_EDGES_S = tuple(round(10.0 ** (e / 8.0), 9)
                    for e in range(-32, 25))


def hist_counts(values) -> List[int]:
    """Counts per LOG_EDGES_S bin (+1 overflow bin at the end;
    values below the first edge land in bin 0)."""
    counts = [0] * (len(LOG_EDGES_S) + 1)
    for v in values:
        counts[bisect.bisect_left(LOG_EDGES_S, float(v))] += 1
    return counts


def _seg_stats(values: List[float]) -> dict:
    vals = sorted(float(v) for v in values)
    return {
        "count": len(vals),
        "mean_s": round(sum(vals) / len(vals), 6),
        "p50_s": round(_pctl(vals, 0.50), 6),
        "p90_s": round(_pctl(vals, 0.90), 6),
        "p99_s": round(_pctl(vals, 0.99), 6),
        "max_s": round(vals[-1], 6),
        "hist_counts": hist_counts(vals),
    }


def _by_rid(spans: List[dict]) -> Dict[str, List[dict]]:
    out: Dict[str, List[dict]] = {}
    for s in spans:
        rid = s.get("rid")
        if rid:
            out.setdefault(rid, []).append(s)
    return out


def prompt_len_bucket(n: int) -> int:
    """Power-of-two prompt-length bucket (the admit ladder's own
    shape discipline): 0, 1..32 -> 32, 33..64 -> 64, ..."""
    n = int(n)
    if n <= 0:
        return 0
    b = 32
    while b < n:
        b <<= 1
    return b


def route_class(recs: List[dict]) -> str:
    """One request's route class from its raw span records:
    ``<admit mode>|<stream|unary>|b<prompt bucket>``. The admit span
    (continuous engine) carries ``mode`` (warm/cold/paged) and the
    admission ``bucket``; the replica's ``http`` span carries the
    ``stream`` flag. Missing spans degrade to ``"?"`` fields — the
    class still groups consistently."""
    mode, bucket = "?", 0
    http_stream = req_stream = None
    for r in recs:
        attrs = r.get("attrs") or {}
        name = r.get("name")
        if name == "admit":
            mode = str(attrs.get("mode", mode))
            try:
                bucket = int(attrs.get("bucket", bucket) or 0)
            except (TypeError, ValueError):
                pass
        elif name == "queue_wait" and not bucket:
            # fallback: older admit spans (pre-ISSUE 14 paged path)
            # carry the bucket only on the queue_wait span
            try:
                bucket = int(attrs.get("bucket", 0) or 0)
            except (TypeError, ValueError):
                pass
        elif name == "http" and "stream" in attrs:
            http_stream = bool(attrs.get("stream"))
        elif name == "request" and "stream" in attrs:
            req_stream = bool(attrs.get("stream"))
    # the replica's handler span is closest to the wire truth; the
    # router's request span covers direct-vs-fleet gaps
    stream = http_stream if http_stream is not None else req_stream
    return (f"{mode}|{'stream' if stream else 'unary'}"
            f"|b{prompt_len_bucket(bucket)}")


def build_service_model(spans: List[dict],
                        client_e2e_by_rid: Optional[dict] = None,
                        stitched_only: bool = True) -> dict:
    """Stitch ``spans`` and fold every request's segment values into
    the versioned model (see module doc). ``stitched_only`` keeps
    single-process orphans out of the distributions (their segments
    are partial by construction); direct-to-replica runs pass False.
    """
    report = reqtrace.stitch_spans(
        spans, client_e2e_by_rid=client_e2e_by_rid)
    recs_by_rid = _by_rid(spans)
    seg_values: Dict[str, List[float]] = {}
    class_values: Dict[str, Dict[str, List[float]]] = {}
    used = 0
    wall_s = attributed_s = 0.0
    for row in report["requests"]:
        if stitched_only and not row.get("stitched"):
            continue
        if row.get("e2e_s") is None:
            continue
        used += 1
        wall_s += float(row["e2e_s"])
        attributed_s += float(row.get("attributed_s", 0.0))
        cls = route_class(recs_by_rid.get(row["rid"], ()))
        for name, v in row["segments"].items():
            seg_values.setdefault(name, []).append(float(v))
            class_values.setdefault(name, {}).setdefault(
                cls, []).append(float(v))
    segments = {}
    for name in sorted(seg_values):
        entry = _seg_stats(seg_values[name])
        entry["classes"] = {
            cls: _seg_stats(vals)
            for cls, vals in sorted(class_values[name].items())}
        segments[name] = entry
    return {
        "version": SERVICE_MODEL_VERSION,
        "generated_t": round(time.time(), 3),
        "edges_s": list(LOG_EDGES_S),
        "counts": {
            "requests": report["counts"]["requests"],
            "stitched": report["counts"]["stitched"],
            "modeled": used,
        },
        "coverage": {
            "stitched_wall_s": round(wall_s, 6),
            "attributed_s": round(attributed_s, 6),
            "frac": (round(attributed_s / wall_s, 4)
                     if wall_s > 0 else None),
        },
        "segments": segments,
    }


def write_service_model(model: dict, path) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(model, indent=2) + "\n")
    return path


def load_service_model(path) -> dict:
    model = json.loads(Path(path).read_text())
    if not isinstance(model, dict) or "segments" not in model:
        raise ValueError(f"{path}: not a service_model.json")
    return model


def drift_report(current: dict, baseline: dict,
                 tolerance: float = 0.25,
                 quantiles=("p50_s", "p99_s"),
                 min_count: int = 3) -> dict:
    """Per-segment distribution drift between two service models.

    For every segment present in either model (with at least
    ``min_count`` samples on the side that has it), each gated
    quantile must sit within ``tolerance`` RELATIVE shift of the
    baseline (both directions — a segment getting 10x *faster* is as
    much a behavior change as 10x slower, and usually means the
    measurement broke). A segment present on one side only is a
    shift. Returns ``{"compared": [...], "shifts": [...],
    "tolerance": ...}``; callers exit nonzero on any shift. A model
    compared against itself passes at tolerance 0 (shift requires a
    STRICT tolerance exceedance)."""
    shifts: List[dict] = []
    compared: List[dict] = []
    if current.get("version") != baseline.get("version"):
        shifts.append({"segment": "<model>", "kind": "version",
                       "current": current.get("version"),
                       "baseline": baseline.get("version")})
    cur_segs = current.get("segments") or {}
    base_segs = baseline.get("segments") or {}
    for name in sorted(set(cur_segs) | set(base_segs)):
        c, b = cur_segs.get(name), base_segs.get(name)
        if c is None or b is None:
            present = c if c is not None else b
            if int(present.get("count", 0)) >= min_count:
                shifts.append({
                    "segment": name, "kind": "missing",
                    "side": "baseline" if c is not None
                    else "current"})
            continue
        if (int(c.get("count", 0)) < min_count
                or int(b.get("count", 0)) < min_count):
            continue                     # too thin to judge either way
        for q in quantiles:
            cv, bv = c.get(q), b.get(q)
            if cv is None or bv is None:
                continue
            rel = abs(float(cv) - float(bv)) / max(abs(float(bv)),
                                                   1e-6)
            row = {"segment": name, "quantile": q,
                   "current": cv, "baseline": bv,
                   "rel_shift": round(rel, 4)}
            compared.append(row)
            if rel > tolerance:
                shifts.append({**row, "kind": "shift"})
    return {"compared": compared, "shifts": shifts,
            "tolerance": tolerance}


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------


class GoodputMeter:
    """Fleet-wide goodput ledger (the router's ``/metrics`` view).

    Three nested token counters, each a subset of the last:

    - ``raw_tokens_total`` — every generated token that crossed the
      wire, whatever became of its request;
    - ``served_tokens_total`` — tokens of requests that completed
      normally: **error / cancelled / deadline-truncated tokens are
      excluded** (the engine burned chip time on them, but nobody got
      the answer they asked for — counting them would reward
      truncation);
    - ``goodput_tokens_total`` — served tokens that ALSO met the
      configured SLO thresholds (== served when no SLO is armed).

    Plus ``deadline_goodput_tokens_total`` (served tokens of
    deadline-carrying requests — the budget was feasible AND met) and
    per-tenant raw/good shares. Rates are over the meter's lifetime
    since its first observation; ``goodput ≤ served ≤ raw`` holds by
    construction.
    """

    #: outcomes whose tokens count as SERVED (the router's _generate
    #: outcome vocabulary; the plain serve.py path passes "ok")
    SERVED_OUTCOMES = ("proxied", "done", "ok")

    def __init__(self, ttft_s: Optional[float] = None,
                 e2e_s: Optional[float] = None):
        self.ttft_s = float(ttft_s) if ttft_s else None
        self.e2e_s = float(e2e_s) if e2e_s else None
        self._lock = threading.Lock()
        self._t0: Optional[float] = None
        self._c = {"raw_tokens_total": 0, "served_tokens_total": 0,
                   "goodput_tokens_total": 0,
                   "deadline_goodput_tokens_total": 0}
        self._tenants: Dict[str, dict] = {}

    def set_slo(self, ttft_s: Optional[float],
                e2e_s: Optional[float]) -> None:
        self.ttft_s = float(ttft_s) if ttft_s else None
        self.e2e_s = float(e2e_s) if e2e_s else None

    def observe(self, tokens: int, outcome: str = "proxied",
                e2e_s: Optional[float] = None,
                ttft_s: Optional[float] = None,
                tenant: str = "default",
                had_deadline: bool = False) -> None:
        tokens = max(int(tokens or 0), 0)
        served = outcome in self.SERVED_OUTCOMES
        slo_ok = served
        if slo_ok and self.ttft_s is not None and ttft_s is not None \
                and ttft_s > self.ttft_s:
            slo_ok = False
        if slo_ok and self.e2e_s is not None and e2e_s is not None \
                and e2e_s > self.e2e_s:
            slo_ok = False
        with self._lock:
            if self._t0 is None:
                self._t0 = time.monotonic()
            self._c["raw_tokens_total"] += tokens
            t = self._tenants.setdefault(
                str(tenant)[:64], {"raw_tokens": 0, "good_tokens": 0})
            t["raw_tokens"] += tokens
            if served:
                self._c["served_tokens_total"] += tokens
                if had_deadline:
                    # a SERVED deadline-carrying request met its
                    # budget by definition (expiry would have
                    # classified it "deadline") — the feasible tier
                    # is a subset of SERVED, not of the SLO tier
                    self._c["deadline_goodput_tokens_total"] += tokens
            if slo_ok:
                self._c["goodput_tokens_total"] += tokens
                t["good_tokens"] += tokens

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._c)
            elapsed = (time.monotonic() - self._t0
                       if self._t0 is not None else 0.0)
            tenants = {k: dict(v) for k, v in self._tenants.items()}
        out["goodput_frac"] = round(
            out["goodput_tokens_total"]
            / max(out["raw_tokens_total"], 1), 4)
        if elapsed > 0:
            out["raw_tok_s"] = round(
                out["raw_tokens_total"] / elapsed, 2)
            out["goodput_tok_s"] = round(
                out["goodput_tokens_total"] / elapsed, 2)
        for t in tenants.values():
            t["goodput_frac"] = round(
                t["good_tokens"] / max(t["raw_tokens"], 1), 4)
        out["goodput_tenants"] = tenants    # JSON-only (nested)
        return out
