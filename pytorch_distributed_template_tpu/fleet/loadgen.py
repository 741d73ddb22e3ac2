"""Trace-replay load harness for the serving fleet.

Serving numbers are only as honest as the traffic that produced them,
so the bench's fleet rung replays a *deterministic trace* — built once
from a seed, identical across arms — instead of ad-hoc request loops:

- **Arrival process**: ``poisson`` (exponential inter-arrivals at
  ``rate_rps``) or ``bursty`` (the same Poisson stream gated by an
  on/off duty cycle at ``burst_factor`` x the rate inside bursts —
  the arrival shape that actually breaks naive admission control).
- **Multi-tenant**: each request carries an ``X-Tenant`` header drawn
  from a weighted tenant mix (the router's WFQ is keyed on it).
- **Shared-prefix mixture**: prompts are ``group prefix + unique
  suffix`` over ``prefix_groups`` seeded groups — the SGLang-style
  workload where cache-aware placement pays. Distinct group tags per
  arm keep arms cold-start comparable.
- **Transport mix**: a ``stream_frac`` fraction rides SSE (yielding
  real TTFT/TPOT per token) and the rest plain JSON; a
  ``cancel_frac`` fraction of streaming requests disconnects
  mid-stream, exercising the router's cancel propagation.

``replay`` drives a trace against any ``/generate`` endpoint (replica
or router) with one thread per request honoring the arrival schedule;
``summarize`` folds the results into the rung's numbers (aggregate
tok/s, TTFT/TPOT p50/p99, shed rate, per-tenant shares). Stdlib-only;
``python -m pytorch_distributed_template_tpu.fleet.loadgen --url ...``
replays from the command line.
"""
from __future__ import annotations

import http.client
import json
import math
import random
import socket
import threading
import time
from typing import Dict, List, Optional
from urllib.parse import urlsplit

from ..utils.promtext import percentile as _percentile


def _diurnal_rate(phase: float, floor: float, sharpness: int) -> float:
    """Unit-peak diurnal envelope at ``phase`` ∈ [0, 1) of the period:
    ``floor + (1-floor)·sin^(2·sharpness)(π·phase)`` — peak 1.0
    mid-period, valley ``floor`` at the edges; higher ``sharpness``
    narrows the peak (more of the period is valley, the shape that
    makes static peak provisioning wasteful)."""
    s = math.sin(math.pi * phase)
    return floor + (1.0 - floor) * (s * s) ** max(int(sharpness), 1)


def _diurnal_cum(floor: float, sharpness: int,
                 n: int = 2048) -> List[float]:
    """Cumulative trapezoid integral of the unit-peak envelope over one
    UNIT period (n+1 knots). Pure arithmetic on fixed inputs — the
    same (floor, sharpness) always yields the same table, so diurnal
    traces stay deterministic without a closed-form ∫sin^2p."""
    cum = [0.0]
    prev = _diurnal_rate(0.0, floor, sharpness)
    for k in range(1, n + 1):
        cur = _diurnal_rate(k / n, floor, sharpness)
        cum.append(cum[-1] + 0.5 * (prev + cur) / n)
        prev = cur
    return cum


def _diurnal_invert(u: float, rate_rps: float, period_s: float,
                    cum: List[float]) -> float:
    """Map a unit-rate Poisson epoch ``u`` to wall time via the inverse
    cumulative envelope Λ⁻¹ (inhomogeneous-Poisson time rescaling):
    whole periods divide out, the remainder binary-searches the table
    and interpolates linearly inside a knot interval."""
    per_period = rate_rps * period_s * cum[-1]
    full, rem = divmod(u, per_period)
    target = rem / (rate_rps * period_s)
    lo, hi = 0, len(cum) - 1
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        if cum[mid] < target:
            lo = mid
        else:
            hi = mid
    seg = cum[hi] - cum[lo]
    frac = (lo + ((target - cum[lo]) / seg if seg > 0 else 0.0)) \
        / (len(cum) - 1)
    return (full + frac) * period_s


def build_trace(n_requests: int, seed: int = 0,
                tenants=("t0", "t1", "t2"),
                tenant_weights: Optional[Dict[str, float]] = None,
                prefix_groups: int = 4, group_tag: str = "g",
                prefix_len: int = 64, suffix_len: int = 16,
                max_new_tokens: int = 8, temperature: float = 0.0,
                arrival: str = "poisson", rate_rps: float = 8.0,
                burst_duty: float = 0.25, burst_factor: float = 6.0,
                burst_period_s: float = 2.0,
                diurnal_period_s: float = 60.0,
                diurnal_floor: float = 0.1,
                diurnal_sharpness: int = 3,
                stream_frac: float = 0.5, cancel_frac: float = 0.0,
                cancel_after_s: float = 0.5,
                deadline_ms: Optional[int] = None,
                infeasible_frac: float = 0.0,
                infeasible_ms: int = 1,
                vocab: int = 256,
                long_prefix_len: int = 0, long_groups: int = 0,
                group_prompt_lens: Optional[List[int]] = None,
                group_max_new: Optional[List[int]] = None,
                group_weights: Optional[List[float]] = None,
                group_stream: Optional[List[bool]] = None
                ) -> List[dict]:
    """Deterministic request trace: same seed ⇒ same trace, byte for
    byte. ``group_tag`` namespaces the prefix groups — two arms with
    different tags share NO prefixes, so each starts cold.

    **Long-prefill mixture (ISSUE 12):** the disaggregation rung needs
    traffic where a minority of LONG prefills contends with
    decode-heavy requests — the workload that collapses a colocated
    replica's TPOT p99. ``long_groups``/``long_prefix_len`` make the
    prompt-length distribution bimodal (the FIRST ``long_groups``
    groups draw ``long_prefix_len``-token prefixes, the rest keep
    ``prefix_len``); ``group_prompt_lens`` pins an explicit per-group
    TOTAL prompt length instead (prefix = entry − ``suffix_len``;
    overrides both), and ``group_max_new`` pins
    a per-group decode budget (long-prefill groups typically pair with
    a small budget, decode-heavy groups with a large one).
    ``group_weights`` biases which group each request draws from
    (uniform when absent — zero-weight groups never draw, so one
    trace shape yields a matched decode-only control arm);
    ``group_stream`` pins per-group SSE transport (the TPOT signal
    needs the decode-heavy groups streaming). All the knobs are
    draw-order-neutral: each group's prefix comes from its OWN seeded
    stream, per-request draws happen knobs-or-not, and overrides
    apply after the draw — so a trace built with the knobs off is
    byte-identical to one built before they existed (the seed
    contract)."""
    rng = random.Random(f"loadgen:{seed}")

    def _group_prefix_len(g: int) -> int:
        if group_prompt_lens is not None:
            return max(int(group_prompt_lens[g % len(
                group_prompt_lens)]) - suffix_len, 0)
        if long_prefix_len > 0 and g < int(long_groups):
            return int(long_prefix_len)
        return int(prefix_len)

    prefixes = []
    for g in range(prefix_groups):
        grng = random.Random(f"prefix:{seed}:{group_tag}:{g}")
        prefixes.append([grng.randrange(1, vocab)
                         for _ in range(_group_prefix_len(g))])
    tenants = list(tenants)
    weights = [float((tenant_weights or {}).get(t, 1.0))
               for t in tenants]
    # arrival times: a Poisson stream, optionally duty-cycle gated into
    # bursts (the gated stream keeps Poisson statistics INSIDE a burst),
    # or rescaled through a deterministic diurnal envelope (ISSUE 19:
    # an inhomogeneous Poisson process whose rate peaks at rate_rps
    # mid-period and idles at diurnal_floor·rate_rps — the traffic
    # shape an autoscaler exists for). Each mode draws ONLY from its
    # own branch, so adding a mode never perturbs another mode's seed
    # stream (the draw-order-neutrality contract).
    times: List[float] = []
    t = 0.0
    burst_rate = rate_rps * burst_factor
    diurnal_u, diurnal_table = 0.0, None
    while len(times) < n_requests:
        if arrival == "poisson":
            t += rng.expovariate(rate_rps)
            times.append(t)
        elif arrival == "bursty":
            t += rng.expovariate(burst_rate)
            if (t % burst_period_s) < burst_duty * burst_period_s:
                times.append(t)
        elif arrival == "diurnal":
            if diurnal_table is None:
                diurnal_table = _diurnal_cum(diurnal_floor,
                                             diurnal_sharpness)
            diurnal_u += rng.expovariate(1.0)
            times.append(_diurnal_invert(
                diurnal_u, rate_rps, diurnal_period_s, diurnal_table))
        else:
            raise ValueError(f"unknown arrival {arrival!r} "
                             "(poisson|bursty|diurnal)")
    trace = []
    for i, at in enumerate(times):
        g = rng.randrange(prefix_groups)
        if group_weights is not None:
            g = rng.choices(range(prefix_groups),
                            weights=group_weights)[0]
        suffix = [rng.randrange(1, vocab) for _ in range(suffix_len)]
        stream = rng.random() < stream_frac
        if group_stream is not None:
            stream = bool(group_stream[g % len(group_stream)])
        cancel = (stream and cancel_frac > 0
                  and rng.random() < cancel_frac)
        # deadline mixture (ISSUE 9): every request carries the
        # feasible budget; an infeasible_frac slice gets a budget that
        # CANNOT be met (these MUST come back 504-classified — they
        # are the deadline-shed arm of the chaos gate, and excluded
        # from the feasible-compliance ratio)
        dl, feasible = None, True
        if deadline_ms is not None:
            dl = int(deadline_ms)
            if infeasible_frac > 0 and rng.random() < infeasible_frac:
                dl, feasible = int(infeasible_ms), False
        trace.append({
            "i": i, "t": round(at, 4),
            # deterministic request id (ISSUE 8): attached as
            # X-Request-Id on replay, so the client-measured TTFT/e2e
            # in this summary JOINS the server-side span timelines per
            # request in the stitcher — same seed, same ids, so two
            # arms of a bench never collide (the group tag namespaces)
            "rid": f"lg-{group_tag}-{seed}-{i:04d}",
            "tenant": rng.choices(tenants, weights=weights)[0],
            "group": f"{group_tag}{g}",
            "prompt_ids": prefixes[g] + suffix,
            "max_new_tokens": int(
                group_max_new[g % len(group_max_new)]
                if group_max_new else max_new_tokens),
            "temperature": float(temperature),
            "stream": stream,
            "cancel_after_s": (float(cancel_after_s) if cancel
                               else None),
            "deadline_ms": dl,
            "deadline_feasible": feasible,
        })
    return trace


def longctx_trace(n_requests: int, seed: int = 0,
                  doc_len: int = 8192, n_docs: int = 2,
                  question_len: int = 24, background_groups: int = 4,
                  doc_frac: float = 0.4, answer_tokens: int = 16,
                  background_new_tokens: int = 48, vocab: int = 256,
                  group_tag: str = "lc", **kw) -> List[dict]:
    """The ``serve_longctx`` trace preset (ISSUE 15 satellite): the
    long-document QA mixture ROADMAP item 2 names — a minority of
    requests share ``n_docs`` long document prefixes (``doc_len``
    tokens, the PR 12 ``long_prefix_len`` knob) followed by a short
    unique question, against a decode-heavy short-prompt background
    (streaming, bigger budgets — the TPOT-p99 signal a monolithic long
    prefill stalls). Pure parameterization of :func:`build_trace`
    (same knobs, same seeded streams), so the draw-order-neutrality
    contract holds by construction — pinned by
    tests/test_longctx.py."""
    groups = int(n_docs) + int(background_groups)
    doc_w = float(doc_frac) / max(int(n_docs), 1)
    bg_w = (1.0 - float(doc_frac)) / max(int(background_groups), 1)
    return build_trace(
        n_requests, seed=seed, prefix_groups=groups,
        group_tag=group_tag, suffix_len=int(question_len),
        long_prefix_len=int(doc_len), long_groups=int(n_docs),
        group_max_new=([int(answer_tokens)] * int(n_docs)
                       + [int(background_new_tokens)]
                       * int(background_groups)),
        group_weights=([doc_w] * int(n_docs)
                       + [bg_w] * int(background_groups)),
        group_stream=([False] * int(n_docs)
                      + [True] * int(background_groups)),
        vocab=vocab, **kw)


def diurnal_trace(n_requests: int, seed: int = 0,
                  peak_rps: float = 6.0, period_s: float = 60.0,
                  floor: float = 0.1, sharpness: int = 3,
                  prefix_groups: int = 4, stream_frac: float = 0.6,
                  group_tag: str = "dn", **kw) -> List[dict]:
    """The ``serve_autoscale`` diurnal/bursty preset (ISSUE 19
    satellite): arrivals follow a deterministic rate envelope that
    peaks at ``peak_rps`` once per ``period_s`` and idles at
    ``floor``·peak between peaks (``sharpness`` narrows the peaks, so
    most of the period is valley — the millions-of-users daily cycle
    compressed to a benchable period). Shared-prefix groups and a
    streaming mixture ride along unchanged so warm/cold and TPOT
    telemetry stay meaningful. Pure parameterization of
    :func:`build_trace` — the draw-order-neutrality contract holds by
    construction, pinned by tests/test_autoscale.py."""
    return build_trace(
        n_requests, seed=seed, arrival="diurnal", rate_rps=peak_rps,
        diurnal_period_s=period_s, diurnal_floor=floor,
        diurnal_sharpness=sharpness, prefix_groups=prefix_groups,
        stream_frac=stream_frac, group_tag=group_tag, **kw)


def prompt_tokens(trace: List[dict]) -> int:
    return sum(len(item["prompt_ids"]) for item in trace)


def _run_one(base: str, item: dict, t_start: float, results: list,
             lock: threading.Lock, timeout_s: float,
             policy: Optional[str]) -> None:
    rec = {"i": item["i"], "rid": item.get("rid"),
           "tenant": item["tenant"],
           "group": item["group"], "stream": item["stream"],
           "prompt_tokens": len(item["prompt_ids"]),
           "ok": False, "shed": False, "cancelled": False,
           "deadline": False,
           "deadline_ms": item.get("deadline_ms"),
           "deadline_feasible": item.get("deadline_feasible", True),
           "tokens": 0, "status": None, "error": None,
           "ttft_s": None, "tpot_s": None, "total_s": None,
           # path provenance (ISSUE 18): the replica's serve-path
           # fingerprint — the X-Serve-Path header on plain JSON
           # responses, the done event's serve_path key on SSE
           "serve_path": None}
    delay = t_start + item["t"] - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    url = urlsplit(base)
    body = {k: item[k] for k in ("prompt_ids", "max_new_tokens",
                                 "temperature")}
    if item["stream"]:
        body["stream"] = True
    headers = {"Content-Type": "application/json",
               "X-Tenant": item["tenant"]}
    if item.get("rid"):
        headers["X-Request-Id"] = item["rid"]
    if item.get("deadline_ms") is not None:
        headers["X-Deadline-Ms"] = str(int(item["deadline_ms"]))
    if policy:
        headers["X-Fleet-Policy"] = policy
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(url.hostname, url.port,
                                      timeout=timeout_s)
    try:
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers=headers)
        resp = conn.getresponse()
        rec["status"] = resp.status
        ct = resp.getheader("Content-Type", "")
        if resp.status == 429:
            rec["shed"] = True
            rec["retry_after"] = resp.getheader("Retry-After")
            resp.read()
        elif resp.status == 504:
            # deadline shed (ISSUE 9): a CLASSIFIED terminal outcome,
            # not an error — the budget spoke, the fleet answered
            rec["deadline"] = True
            resp.read()
        elif resp.status != 200:
            rec["error"] = f"http {resp.status}"
            resp.read()
        elif ct.startswith("text/event-stream"):
            _consume_sse(resp, conn, item, rec, t0)
        else:
            rec["serve_path"] = resp.getheader("X-Serve-Path")
            data = json.loads(resp.read().decode("utf-8"))
            rec["tokens"] = len(data.get("ids") or ())
            rec["ok"] = True
            if data.get("stop_reason") == "deadline":
                rec["deadline"] = True   # served, but truncated
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
        rec["total_s"] = round(time.monotonic() - t0, 4)
        with lock:
            results.append(rec)


def _sse_socket(resp, conn):
    """The live socket under an SSE response. With HTTP/1.0
    close-delimited responses http.client detaches the socket from the
    connection at ``getresponse()`` (``conn.sock`` is None) — the
    response's buffered reader holds it."""
    sock = getattr(conn, "sock", None)
    if sock is None:
        raw = getattr(getattr(resp, "fp", None), "raw", None)
        sock = getattr(raw, "_sock", None)
    return sock


def _consume_sse(resp, conn, item: dict, rec: dict,
                 t0: float) -> None:
    """Read ``data:`` events until done; first token delta stamps TTFT,
    the delta cadence yields TPOT. A ``cancel_after_s`` request closes
    the connection mid-stream (the router propagates the disconnect as
    a slot-engine cancel)."""
    cancel_after = item.get("cancel_after_s")
    sock = _sse_socket(resp, conn) if cancel_after is not None else None
    t_first = t_last = None
    try:
        while True:
            if cancel_after is not None:
                elapsed = time.monotonic() - t0
                if elapsed >= cancel_after or sock is None:
                    rec["cancelled"] = True
                    rec["ok"] = True   # a deliberate cancel = success
                    return
                sock.settimeout(cancel_after - elapsed)
            try:
                line = resp.readline()
            except (socket.timeout, OSError):
                rec["cancelled"] = True
                rec["ok"] = True
                return
            if not line:
                dl = item.get("deadline_ms")
                if (dl is not None and (time.monotonic() - t0)
                        >= dl / 1e3):
                    # the router truncated the stream at the deadline
                    # (ISSUE 9): a classified terminal outcome — the
                    # client's own clock agrees the budget is spent
                    rec["deadline"] = True
                else:
                    rec["error"] = rec["error"] or "stream truncated"
                return
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[len(b"data: "):])
            if "error" in event:
                rec["error"] = event["error"]
                return
            now = time.monotonic()
            if event.get("done"):
                rec["tokens"] = (len(event.get("ids") or ())
                                 or rec["tokens"])
                rec["ok"] = True
                rec["serve_path"] = event.get("serve_path")
                if event.get("stop_reason") == "deadline":
                    rec["deadline"] = True   # served, but truncated
                if (t_first is not None and t_last is not None
                        and rec["tokens"] > 1 and t_last > t_first):
                    rec["tpot_s"] = round(
                        (t_last - t_first) / (rec["tokens"] - 1), 5)
                return
            ids = event.get("ids") or ()
            if ids:
                if t_first is None:
                    t_first = now
                    rec["ttft_s"] = round(now - t0, 4)
                else:
                    # per-TOKEN inter-delta gap (normalized by the
                    # delta's token count): TPOT is a per-token
                    # metric, and pooling these across streams is
                    # what makes a single long-prefill stall visible
                    # at p99 (the serve_disagg gate's signal)
                    rec.setdefault("tpot_gaps", []).append(
                        round((now - t_last) / len(ids), 5))
                t_last = now
                rec["tokens"] += len(ids)
    finally:
        # conn.close() alone cannot reach a detached socket — closing
        # the RESPONSE is what actually hangs up (the cancel signal)
        try:
            resp.close()
        except OSError:
            pass


def replay(base_url: str, trace: List[dict], timeout_s: float = 120.0,
           policy: Optional[str] = None) -> dict:
    """Replay a trace against ``base_url`` honoring its arrival
    schedule (one thread per request). Returns ``{"results": [...],
    "wall_s": ...}``."""
    results: List[dict] = []
    lock = threading.Lock()
    t_start = time.monotonic() + 0.05
    threads = [
        threading.Thread(target=_run_one,
                         args=(base_url, item, t_start, results, lock,
                               timeout_s, policy),
                         daemon=True)
        for item in trace
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s + trace[-1]["t"] + 30.0)
    wall_s = time.monotonic() - t_start
    return {"results": results, "wall_s": round(wall_s, 3)}


def summarize(replayed: dict, trace: Optional[List[dict]] = None,
              slo_ttft_s: Optional[float] = None,
              slo_e2e_s: Optional[float] = None) -> dict:
    """Fold a replay into the rung's numbers. TTFT/TPOT percentiles
    come from the streaming subset (the only honest first-token
    signal); aggregate tok/s counts every generated token over the
    replay wall clock.

    **Goodput (ISSUE 14):** ``slo_compliant_tok_s`` counts only the
    tokens of requests that completed normally — deadline-truncated,
    cancelled, and errored tokens are EXCLUDED — and (when
    ``slo_ttft_s``/``slo_e2e_s`` are given) also met the SLO; the
    per-tenant ``compliance_frac`` is each tenant's share of its own
    tokens that qualified. Percentile math stays on the one package
    convention (utils/promtext.percentile) — no new implementations."""
    results = replayed["results"]
    wall_s = max(replayed["wall_s"], 1e-9)
    ttfts = sorted(r["ttft_s"] for r in results
                   if r["ttft_s"] is not None)
    tpots = sorted(r["tpot_s"] for r in results
                   if r["tpot_s"] is not None)
    # pooled per-token gaps across every stream (see _consume_sse):
    # the per-TOKEN TPOT distribution, orders of magnitude more
    # samples than the per-request means above
    gaps = sorted(g for r in results
                  for g in (r.get("tpot_gaps") or ()))
    totals = sorted(r["total_s"] for r in results
                    if r["ok"] and r["total_s"] is not None)
    n = len(results)
    shed = sum(r["shed"] for r in results)
    errors = sum(1 for r in results if r["error"])
    tokens = sum(r["tokens"] for r in results)

    def _compliant(r) -> bool:
        # goodput classification: served normally (no error, no
        # deliberate cancel, not deadline-truncated) AND inside the
        # SLO thresholds when armed
        if not r["ok"] or r["error"] or r["cancelled"] \
                or r["deadline"]:
            return False
        if (slo_ttft_s is not None and r["ttft_s"] is not None
                and r["ttft_s"] > slo_ttft_s):
            return False
        if (slo_e2e_s is not None and r["total_s"] is not None
                and r["total_s"] > slo_e2e_s):
            return False
        return True

    compliant_tokens = sum(r["tokens"] for r in results
                           if _compliant(r))
    per_tenant: Dict[str, dict] = {}
    for r in results:
        t = per_tenant.setdefault(
            r["tenant"], {"requests": 0, "ok": 0, "shed": 0,
                          "tokens": 0, "compliant_tokens": 0})
        t["requests"] += 1
        t["ok"] += int(r["ok"])
        t["shed"] += int(r["shed"])
        t["tokens"] += r["tokens"]
        if _compliant(r):
            t["compliant_tokens"] += r["tokens"]
    for t in per_tenant.values():
        t["compliance_frac"] = round(
            t["compliant_tokens"] / max(t["tokens"], 1), 4)
    # per-serve-path latency/error split (ISSUE 18): the client-side
    # join of the provenance fingerprint — "warm_adopt is slower than
    # warm" or "every error rode the pull path" falls out of this
    # table instead of a per-request grep
    by_path: Dict[str, dict] = {}
    for r in results:
        fp = r.get("serve_path")
        if not fp:
            continue
        b = by_path.setdefault(fp, {
            "requests": 0, "ok": 0, "errors": 0, "deadline_hit": 0,
            "tokens": 0, "_totals": [], "_ttfts": []})
        b["requests"] += 1
        b["ok"] += int(r["ok"])
        b["errors"] += int(bool(r["error"]))
        b["deadline_hit"] += int(r["deadline"])
        b["tokens"] += r["tokens"]
        if r["total_s"] is not None and r["ok"]:
            b["_totals"].append(r["total_s"])
        if r["ttft_s"] is not None:
            b["_ttfts"].append(r["ttft_s"])
    for b in by_path.values():
        totals_fp = sorted(b.pop("_totals"))
        ttfts_fp = sorted(b.pop("_ttfts"))
        b["latency_p50_s"] = _percentile(totals_fp, 0.5)
        b["latency_p99_s"] = _percentile(totals_fp, 0.99)
        b["ttft_p50_s"] = _percentile(ttfts_fp, 0.5)
    # terminal-outcome accounting (ISSUE 9): a request is STRANDED
    # when it never reached ANY classified outcome — no HTTP status,
    # no deliberate cancel (client-side timeouts and connect failures
    # land here), or its worker thread never even reported. A chaos run
    # wants stranded == 0: every fault must resolve to a
    # classified terminal state, never a silent hang.
    stranded = sum(1 for r in results
                   if r["status"] is None and not r["cancelled"]
                   and not r["deadline"])
    missing = (len(trace) - n) if trace is not None else 0
    deadline_hit = sum(r["deadline"] for r in results)
    feasible = [r for r in results
                if r.get("deadline_ms") is not None
                and r.get("deadline_feasible", True)]
    feasible_ok = sum(1 for r in feasible
                      if r["ok"] and not r["deadline"])
    out = {
        "requests": n,
        "ok": sum(r["ok"] for r in results),
        "shed": shed,
        "errors": errors,
        "cancelled": sum(r["cancelled"] for r in results),
        "deadline_hit": deadline_hit,
        "stranded": stranded + missing,
        "deadline_feasible": len(feasible),
        "deadline_compliance": (round(feasible_ok / len(feasible), 4)
                                if feasible else None),
        "shed_rate": round(shed / n, 4) if n else 0.0,
        "error_rate": round(errors / n, 4) if n else 0.0,
        "tokens_out": tokens,
        "agg_tok_s": round(tokens / wall_s, 2),
        # goodput (ISSUE 14): the useful-work rate — compliant tokens
        # only, over the same wall clock as agg_tok_s (so goodput <=
        # raw by construction)
        "slo_compliant_tokens": compliant_tokens,
        "slo_compliant_tok_s": round(compliant_tokens / wall_s, 2),
        "goodput_frac": round(compliant_tokens / max(tokens, 1), 4),
        "wall_s": round(wall_s, 3),
        "ttft_p50_s": _percentile(ttfts, 0.5),
        "ttft_p99_s": _percentile(ttfts, 0.99),
        "tpot_p50_s": _percentile(tpots, 0.5),
        "tpot_p99_s": _percentile(tpots, 0.99),
        "tpot_tok_p50_s": _percentile(gaps, 0.5),
        "tpot_tok_p99_s": _percentile(gaps, 0.99),
        "latency_p50_s": _percentile(totals, 0.5),
        "latency_p99_s": _percentile(totals, 0.99),
        "per_tenant": per_tenant,
        "by_path": dict(sorted(by_path.items())),
        # per-request client measurements keyed by rid: the stitcher
        # (scripts/trace_stitch.py --client) joins these onto the
        # server-side span timelines, so attribution is against the
        # CLIENT-measured e2e, residual included
        "by_request": [
            {"rid": r.get("rid"), "tenant": r["tenant"],
             "ok": r["ok"], "shed": r["shed"], "status": r["status"],
             "tokens": r["tokens"], "ttft_s": r["ttft_s"],
             "total_s": r["total_s"],
             "serve_path": r.get("serve_path")}
            for r in sorted(results, key=lambda r: r["i"])],
    }
    if trace is not None:
        out["prompt_tokens"] = prompt_tokens(trace)
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="trace-replay load generator for /generate "
                    "endpoints (fleet router or a single serve.py)")
    p.add_argument("--url", required=True,
                   help="base URL, e.g. http://127.0.0.1:8900")
    p.add_argument("--n", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--arrival", default="poisson",
                   choices=("poisson", "bursty", "diurnal"))
    p.add_argument("--rate", type=float, default=8.0, metavar="RPS")
    p.add_argument("--tenants", default="t0,t1,t2")
    p.add_argument("--prefix-groups", type=int, default=4)
    p.add_argument("--prefix-len", type=int, default=64)
    p.add_argument("--long-prefix-len", type=int, default=0,
                   help="bimodal prompt-length mixture (ISSUE 12): "
                        "the first --long-groups prefix groups draw "
                        "prefixes this long (0 = unimodal)")
    p.add_argument("--long-groups", type=int, default=0,
                   help="how many leading prefix groups are LONG")
    p.add_argument("--suffix-len", type=int, default=16)
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--stream-frac", type=float, default=0.5)
    p.add_argument("--cancel-frac", type=float, default=0.0)
    p.add_argument("--group-tag", default="g")
    p.add_argument("--policy", default=None,
                   help="X-Fleet-Policy override (cache_aware|"
                        "least_loaded|round_robin)")
    p.add_argument("--timeout-s", type=float, default=120.0)
    p.add_argument("--preset", default=None,
                   choices=("longctx", "diurnal"),
                   help="named trace preset: 'longctx' = the "
                        "serve_longctx long-document QA mixture "
                        "(shared --long-prefix-len document prefixes "
                        "+ short questions vs a decode-heavy "
                        "streaming background, ISSUE 15); 'diurnal' = "
                        "the serve_autoscale diurnal/bursty envelope "
                        "(--rate is the PEAK rps, ISSUE 19)")
    p.add_argument("--doc-len", type=int, default=8192,
                   help="longctx preset: shared document prefix "
                        "length in tokens")
    p.add_argument("--n-docs", type=int, default=2,
                   help="longctx preset: distinct shared documents")
    p.add_argument("--diurnal-period-s", type=float, default=60.0,
                   help="diurnal: seconds per peak-to-peak cycle")
    p.add_argument("--diurnal-floor", type=float, default=0.1,
                   help="diurnal: valley rate as a fraction of peak")
    p.add_argument("--diurnal-sharpness", type=int, default=3,
                   help="diurnal: peak narrowness exponent (sin^2p)")
    args = p.parse_args(argv)
    if args.preset == "longctx":
        trace = longctx_trace(
            args.n, seed=args.seed, doc_len=args.doc_len,
            n_docs=args.n_docs, group_tag=args.group_tag,
            tenants=[t for t in args.tenants.split(",") if t],
            arrival=args.arrival, rate_rps=args.rate)
    elif args.preset == "diurnal":
        trace = diurnal_trace(
            args.n, seed=args.seed, peak_rps=args.rate,
            period_s=args.diurnal_period_s, floor=args.diurnal_floor,
            sharpness=args.diurnal_sharpness,
            prefix_groups=args.prefix_groups,
            group_tag=args.group_tag, prefix_len=args.prefix_len,
            suffix_len=args.suffix_len,
            max_new_tokens=args.max_new_tokens,
            stream_frac=args.stream_frac,
            tenants=[t for t in args.tenants.split(",") if t])
    else:
        trace = build_trace(
            args.n, seed=args.seed,
            tenants=[t for t in args.tenants.split(",") if t],
            prefix_groups=args.prefix_groups, group_tag=args.group_tag,
            prefix_len=args.prefix_len, suffix_len=args.suffix_len,
            max_new_tokens=args.max_new_tokens, arrival=args.arrival,
            rate_rps=args.rate, stream_frac=args.stream_frac,
            cancel_frac=args.cancel_frac,
            long_prefix_len=args.long_prefix_len,
            long_groups=args.long_groups)
    summary = summarize(replay(args.url, trace,
                               timeout_s=args.timeout_s,
                               policy=args.policy), trace)
    print(json.dumps(summary, indent=2))
    return 0 if summary["errors"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
