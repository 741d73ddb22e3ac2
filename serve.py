"""Minimal HTTP serving front-end: load once, generate per request.

Completes the serving story at the network boundary (the reference has
no inference path at all, /root/reference/test.py is batch eval): the
same checkpoint-or-artifact loading as ``generate.py``
(engine/serving.load_generation_stack — training checkpoints, w8a16 /
merged-LoRA params-only artifacts, recovered BPE tokenizer), wrapped
in a stdlib ``ThreadingHTTPServer``. No web framework, no deps.

    python serve.py -r saved/<lm>/train/<run>/model_best --port 8000

    GET  /healthz             -> {"status": "ok", "arch": ...,
                              "last_anomaly_step": null | int, ...}
    GET  /metrics             -> Prometheus text exposition (request /
                              token / cancellation counters, queue
                              depth, live slots, latency percentiles,
                              anomaly / straggler-window / profile-
                              capture totals, supervisor restart
                              counters when supervised);
                              ?format=json for the same as JSON
    POST /profile?steps=N     -> on-demand jax.profiler capture windowed
                              on the scheduler's progress counters
                              (&timeout_s=S, default 30); responds when
                              the capture closes, 409 if one is running
    POST /generate            body: {"prompt": "text"} or
                              {"prompt_ids": [1, 2, 3]}, optional
                              max_new_tokens / temperature / top_k /
                              top_p / seed / speculative / stop /
                              stream
                              -> {"text": ...} and/or {"ids": [...]},
                              "stop_reason": "stop" | "length"

``stream: true`` switches the response to server-sent events
(``text/event-stream``): one ``data: {"ids": [...]}`` event per
decoded chunk as the continuous scheduler absorbs it (the deltas
concatenate to the final ids), then a final ``data:`` event with the
complete normal response plus ``"done": true``. Schedulers without
incremental decode (static groups, speculative requests) send one
delta covering the whole generation — same wire shape either way.
A mid-stream client disconnect CANCELS the generation on the slot
engine: the row finalizes at its next chunk absorb and its slot
frees for waiting traffic instead of decoding out the rest of its
budget (``cancelled`` count in ``/healthz`` batching stats).

``stop``: stop-token ids and/or single-token strings (a list or one
value). Generation for a row ends as soon as it emits a stop token —
the in-graph loop exits once EVERY row in the batch is done, so
early-stopping requests stop burning chip time on the rest of their
budget. The stop token is stripped from the response; ``stop_reason``
says whether the row stopped or ran out its budget. Requests with
different stop sets still share a batch (per-row stop sets in the
executable).

A ``serving.prefix_cache`` config block (or ``--prefix-cache on``)
attaches the paged KV block pool + radix prefix index
(engine/kvcache.py, docs/SERVING.md): requests sharing a cached prompt
prefix admit as an HBM block copy plus a suffix-only prefill instead
of recomputing the whole prompt — hit/eviction/occupancy counters ride
``GET /metrics`` and the per-chunk telemetry JSONL.

Concurrent requests batch. On RoPE / non-rolling-cache models the
default is CONTINUOUS batching (engine/continuous.py, ``--scheduler
auto``): a slot engine over one shared KV cache where requests admit
mid-flight, decode in chunked in-graph steps with per-row budgets /
stop sets / sampling params (no group keys — ANY mix of requests
shares the engine), and free their slot the moment they stop;
``/healthz`` reports slot stats and end-to-end latency percentiles.
Absolute-position and rolling-window models fall back to the STATIC
micro-batch scheduler (engine/serving.BatchedGenerationService): a
worker groups compatible requests — same max_new_tokens and sampling
config, prompt lengths within a 128-token bucket for RoPE families
(shorter rows left-pad with per-row masking; absolute-position and
rolling-window models group by exact length) — that arrive within
``--batch-window-ms`` (default 25 ms) into one batched prefill +
shared decode loop, up to ``--max-batch`` rows. Each request keeps its
own sampling stream, so responses don't depend on batch composition
(token-exact up to float-level ties between the batched and solo
kernels), and speculative requests run batch-1 with an acceptance
probe: the first chunk measures tokens/call, and requests whose
acceptance projects a loss finish with plain decode
(``speculation_disabled: true`` in the response's ``speculative``
stats; greedy output is identical either way). ``GET /healthz``
reports batching stats (requests/batches/max_batch_size). The first
request per (sampling-config, shape) pays the XLA compile; later ones
reuse the cached executables (engine/generate._decode_fns).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from pytorch_distributed_template_tpu.config import ConfigParser  # noqa: E402
import pytorch_distributed_template_tpu.data  # noqa: F401,E402
import pytorch_distributed_template_tpu.engine  # noqa: F401,E402
import pytorch_distributed_template_tpu.models  # noqa: F401,E402
from pytorch_distributed_template_tpu.engine.continuous import (  # noqa: E402
    ContinuousBatchingService,
)
from pytorch_distributed_template_tpu.engine.kvcache import (  # noqa: E402
    serialize_pages,
)
from pytorch_distributed_template_tpu.engine.serving import (  # noqa: E402
    BatchedGenerationService, DeadlineExceeded, GenerationService,
    load_generation_stack,
)
from pytorch_distributed_template_tpu.resilience import faults  # noqa: E402
from pytorch_distributed_template_tpu.observability.health import (  # noqa: E402
    health_counters,
)
from pytorch_distributed_template_tpu.observability.profiler import (  # noqa: E402
    OnDemandProfiler,
)
from pytorch_distributed_template_tpu.observability.audit import (  # noqa: E402
    ShadowAuditor,
)
from pytorch_distributed_template_tpu.observability.reqtrace import (  # noqa: E402
    DEADLINE_EXPIRED_HEADER, DEADLINE_HEADER, Deadline, RequestTracer,
    SERVE_PATH_HEADER, SloWatcher, mint_request_id, sanitize_request_id,
)
from pytorch_distributed_template_tpu.observability.telemetry import (  # noqa: E402
    compile_cache_stats,
)
from pytorch_distributed_template_tpu.observability.timeseries import (  # noqa: E402
    TimeSeriesStore, set_default_store,
)
from pytorch_distributed_template_tpu.resilience.supervisor import (  # noqa: E402
    ENV_EVENTS, EXIT_PREEMPTED, read_supervisor_stats,
)
from pytorch_distributed_template_tpu.utils.promtext import (  # noqa: E402
    prometheus_text,
)
from pytorch_distributed_template_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)


def supervisor_restart_stats() -> dict:
    """Restart counters from the resilience supervisor's lifecycle log.

    A supervised process inherits ``PDT_SUPERVISOR_EVENTS`` from
    ``scripts/supervise.py``; unsupervised servers fall back to a
    ``supervisor.jsonl`` in the working directory, and {} when neither
    exists. Re-read per scrape — the file is a handful of lines."""
    path = os.environ.get(ENV_EVENTS, "supervisor.jsonl")
    if not os.path.exists(path):
        return {}
    try:
        stats = read_supervisor_stats(path)
    except OSError:
        return {}
    return {
        "restarts_total": int(stats["restarts_total"]),
        "last_restart_cause": stats["last_restart_cause"],
    }


def _run_request(service: GenerationService, req: dict,
                 on_tokens=None, cancel=None,
                 request_id=None, deadline=None) -> dict:
    """JSON request body -> GenerationService.generate kwargs. All
    encoding/validation/dispatch logic lives in the service (shared
    with generate.py); this only maps the wire format. ``request_id``
    is the trace id from the ``X-Request-Id`` header (minted here when
    the client sent none) — it keys the request's spans end to end.
    ``deadline`` is the parsed ``X-Deadline-Ms`` budget (ISSUE 9)."""
    kwargs = dict(
        prompt=req.get("prompt"),
        prompt_ids=req.get("prompt_ids"),
        max_new_tokens=int(req.get("max_new_tokens", 64)),
        temperature=float(req.get("temperature", 0.0)),
        top_k=int(req.get("top_k", 0)),
        top_p=float(req.get("top_p", 0.0)),
        seed=int(req.get("seed", 0)),
        speculative=int(req.get("speculative", 0)),
        stop=req.get("stop"),
        request_id=request_id,
        deadline=deadline,
    )
    if on_tokens is not None:
        kwargs["on_tokens"] = on_tokens
    if cancel is not None:
        kwargs["cancel"] = cancel
    return service.generate(**kwargs)


def audit_record(req: dict, out: dict) -> dict:
    """Wire request + finished response -> ShadowAuditor record: the
    sampling config a replay takes (same defaults as ``_run_request``
    so the reference decodes the request the server actually ran) plus
    the served ids / fingerprint / stop_reason the verdict compares."""
    return {
        "rid": out.get("request_id"),
        "serve_path": out.get("serve_path"),
        "ids": out.get("ids"),
        "stop_reason": out.get("stop_reason"),
        "prompt": req.get("prompt"),
        "prompt_ids": req.get("prompt_ids"),
        "max_new_tokens": int(req.get("max_new_tokens", 64)),
        "temperature": float(req.get("temperature", 0.0)),
        "top_k": int(req.get("top_k", 0)),
        "top_p": float(req.get("top_p", 0.0)),
        "seed": int(req.get("seed", 0)),
        "stop": req.get("stop"),
    }


def service_metrics(service: GenerationService, auditor=None) -> dict:
    """Scheduler-agnostic metrics snapshot for ``GET /metrics``.

    Counters come from the service's ``stats`` dict (every scheduler
    maintains one; the continuous engine's is richest), queue depth and
    live slots from the slot engine's accessors when present (0/absent
    otherwise — the plain serialized service has no queue)."""
    stats = dict(getattr(service, "stats", None) or {})
    out = {
        "scheduler": type(service).__name__,
        # the static scheduler increments "requests" only after a batch
        # finishes generating (engine/serving._run_batch), so falling
        # back to it for "completed" stays truthful; the continuous
        # engine tracks both explicitly
        "requests_total": int(
            stats.get("requests", stats.get("completed", 0))),
        "requests_completed": int(
            stats.get("completed", stats.get("requests", 0))),
        "tokens_generated_total": int(stats.get("tokens_generated", 0)),
        "cancelled_total": int(stats.get("cancelled", 0)),
        "queue_depth": int(
            service.queue_depth() if hasattr(service, "queue_depth")
            else getattr(service, "_queue", None).qsize()
            if getattr(service, "_queue", None) is not None else 0),
        "live_slots": int(
            service.live_slots() if hasattr(service, "live_slots") else 0),
        # named without the _total suffix: it's a capacity gauge, not a
        # monotonic counter (prometheus_text infers TYPE from the name)
        "slots": int(getattr(service, "_slots", 0)
                     or getattr(service, "_max_batch", 0) or 1),
    }
    for k in ("batches", "chunks", "admissions", "eras", "max_active",
              "batched_requests", "max_batch_size"):
        if k in stats:
            out[k] = int(stats[k])
    # disaggregated serving (ISSUE 12): the replica's role (string —
    # JSON-only; prometheus_text emits numeric series), its DP group
    # count, and the handoff counters: prefills exported for shipping
    # and remote page chains ingested
    out["role"] = str(getattr(service, "role", "both"))
    out["dp_groups"] = int(stats.get("dp_groups", 1) or 1)
    out["prefill_exports_total"] = int(stats.get("prefill_exports", 0))
    out["remote_admits_total"] = int(stats.get("remote_admits", 0))
    # deadline + brownout counters (ISSUE 9); _total suffix = counter
    # TYPE for the prometheus exposition
    out["deadline_expired_total"] = int(
        stats.get("deadline_expired", 0))
    out["brownout_clamped_total"] = int(
        stats.get("brownout_clamped", 0))
    # ONE monotonic progress counter for the fleet poller's wedged-
    # replica detection (ISSUE 9): any scheduler activity advances it,
    # so "frozen progress + pending work + healthy /healthz" is the
    # wedge signature. Summing the per-scheduler counters keeps it
    # scheduler-agnostic (each term is itself monotonic).
    out["scheduler_progress_total"] = (
        int(stats.get("chunks", 0)) + int(stats.get("batches", 0))
        + int(stats.get("admissions", 0))
        + int(stats.get("completed", stats.get("requests", 0)))
        + int(stats.get("tokens_generated", 0)))
    # brownout ladder (ISSUE 9): level gauge + transition counters;
    # schedulers without a controller read level 0
    if hasattr(service, "brownout_stats"):
        out.update(service.brownout_stats())
    else:
        out["brownout_level"] = 0
    if hasattr(service, "latency_percentiles"):
        out["latency"] = service.latency_percentiles()
    # paged prefix-cache counters (engine/kvcache): hit tokens are
    # prompt tokens served from the pool instead of recomputed; the
    # pool gauges expose occupancy so operators can size
    # serving.prefix_cache.pool_blocks from live traffic
    prefix = (service.prefix_cache_stats()
              if hasattr(service, "prefix_cache_stats") else None)
    if prefix is not None:
        out["prefix_hit_tokens_total"] = int(prefix["prefix_hit_tokens"])
        out["prefix_hit_requests_total"] = int(
            prefix["prefix_hit_requests"])
        out["prefix_lookups_total"] = int(prefix["prefix_lookups"])
        out["prefix_inserted_blocks_total"] = int(
            prefix["prefix_inserted_blocks"])
        out["prefix_evictions_total"] = int(prefix["prefix_evictions"])
        out["prefix_dropped_inserts_total"] = int(
            prefix["prefix_dropped_inserts"])
        out["prefix_hit_rate"] = float(prefix["prefix_hit_rate"])
        out["prefix_pool_blocks"] = int(prefix["prefix_pool_blocks"])
        out["prefix_pool_blocks_used"] = int(
            prefix["prefix_pool_blocks_used"])
        # occupancy WITHOUT double counting (ISSUE 7): resident =
        # unique sharable pages the radix index owns; referenced =
        # pages live requests actually read/write. On the scatter
        # fallback a hot prefix is resident AND copied per-slot — the
        # split makes that visible.
        out["prefix_pool_blocks_resident"] = int(
            prefix["prefix_pool_blocks_resident"])
        out["prefix_pool_blocks_referenced"] = int(
            prefix["prefix_pool_blocks_referenced"])
        out["prefix_adopted_blocks_total"] = int(
            prefix["prefix_adopted_blocks"])
        # the ISSUE 7 gate, observable in production: device bytes warm
        # admits copied (paged path: 0 — admits are pointer updates)
        # and the fraction of decode chunks served by the paged path
        out["warm_admit_copy_bytes_total"] = int(
            prefix["warm_admit_copy_bytes"])
        # page shipping (ISSUE 12): blocks exported to / imported from
        # peer replicas' pools and the raw page bytes that crossed — a
        # decode replica's warm_admit_copy_bytes_total above equals
        # page_ship_in_bytes_total exactly (gated in serve_disagg)
        out["pages_shipped_total"] = int(
            prefix.get("pages_exported", 0))
        out["pages_imported_total"] = int(
            prefix.get("pages_imported", 0))
        out["page_ship_out_bytes_total"] = int(
            prefix.get("page_ship_out_bytes", 0))
        out["page_ship_in_bytes_total"] = int(
            prefix.get("page_ship_in_bytes", 0))
        out["page_ship_dropped_total"] = int(
            prefix.get("page_ship_dropped", 0))
        # tiered KV spill hierarchy (ISSUE 13): demote/promote
        # traffic, checksum verdicts, degradation counters, and the
        # per-tier occupancy gauges (no _total suffix) riding the
        # resident/referenced split above
        out["tier_demoted_blocks_total"] = int(
            prefix.get("tier_demoted_blocks", 0))
        out["tier_promoted_blocks_total"] = int(
            prefix.get("tier_promoted_blocks", 0))
        out["tier_demote_bytes_total"] = int(
            prefix.get("tier_demote_bytes", 0))
        out["tier_promote_bytes_total"] = int(
            prefix.get("tier_promote_bytes", 0))
        out["tier_checksum_failures_total"] = int(
            prefix.get("tier_checksum_failures", 0))
        out["tier_exhaust_drops_total"] = int(
            prefix.get("tier_exhaust_drops", 0))
        out["tier_demote_errors_total"] = int(
            prefix.get("tier_demote_errors", 0))
        out["tier_host_blocks"] = int(
            prefix.get("tier_host_blocks", 0))
        out["tier_host_bytes"] = int(prefix.get("tier_host_bytes", 0))
        out["tier_disk_blocks"] = int(
            prefix.get("tier_disk_blocks", 0))
        out["tier_disk_bytes"] = int(prefix.get("tier_disk_bytes", 0))
        out["peer_exports_total"] = int(stats.get("peer_exports", 0))
        # long-context serving (ISSUE 15): chunked-streaming-prefill
        # counters, the pool's layout gauges (page bytes make the int8
        # HBM saving scrapeable; window exposes the ring), and the
        # per-reason pool-fallback counters — flat names, the repo's
        # labeled-family convention (reason rides in the name)
        out["prefill_chunks_total"] = int(
            stats.get("prefill_chunks", 0))
        out["streamed_prefill_tokens_total"] = int(
            stats.get("streamed_prefill_tokens", 0))
        out["streamed_requests_total"] = int(
            stats.get("streamed_requests", 0))
        out["prefix_page_bytes"] = int(
            prefix.get("prefix_page_bytes", 0))
        out["prefix_pool_window"] = int(
            prefix.get("prefix_pool_window", 0))
        out["prefix_pool_kv_quant"] = int(
            prefix.get("prefix_pool_kv_quant", 0))
        for reason in ("window", "kv_quant", "undersized",
                       "gpt2_layout", "dry_pool"):
            out[f"pool_fallback_{reason}_total"] = int(
                prefix.get(f"pool_fallback_{reason}", 0))
        out["pool_fallback_total"] = int(
            prefix.get("pool_fallback_total", 0))
        # batched prefill export (ISSUE 13 satellite): lock
        # acquisitions amortized over export bursts
        out["prefill_export_batches_total"] = int(
            stats.get("prefill_export_batches", 0))
        out["prefill_export_max_batch"] = int(
            stats.get("prefill_export_max_batch", 0))
        chunks = int(stats.get("chunks", 0) or 0)
        if chunks:
            out["paged_decode_frac"] = round(
                int(stats.get("paged_chunks", 0)) / chunks, 4)
        else:
            # plain scheduler (or no traffic yet): derive from which
            # arm actually served each batch-1 request — a
            # paged-CAPABLE pool whose traffic all fell back to the
            # scatter arm must NOT read 1.0
            served = (int(prefix.get("batch1_paged_requests", 0))
                      + int(prefix.get("batch1_scatter_requests", 0)))
            out["paged_decode_frac"] = (
                round(int(prefix.get("batch1_paged_requests", 0))
                      / served, 4) if served else 0.0)
    if prefix is None and getattr(service, "pool_refusal_reason", ""):
        # the pool REFUSED to construct (unsupported layout, ISSUE 15
        # satellite): every served request ran without it — counted at
        # the response funnel (engine/serving._response) and attributed
        # to the machine-readable refusal reason so fleet-level
        # fallback is visible, not a one-line log
        reason = str(service.pool_refusal_reason)
        refused = int(stats.get("pool_refused_requests", 0))
        # "unsupported" = a refusal without a machine-readable reason
        # (a plain ValueError) — still split out so the per-reason
        # family always sums to the total
        for r in ("window", "kv_quant", "undersized", "gpt2_layout",
                  "unsupported"):
            out[f"pool_fallback_{r}_total"] = (
                refused if r == reason else 0)
        out["pool_fallback_total"] = refused
    # persistent-compile-cache counters (utils/compile_cache): a miss is
    # a real XLA compile, a hit an executable read back from disk —
    # restart cost and mid-traffic recompile storms as scrapeable series
    cache = compile_cache_stats()
    out["compile_cache_hits_total"] = int(cache["hits"])
    out["compile_cache_misses_total"] = int(cache["misses"])
    # tensor-parallel serving (ISSUE 10): tp_degree gauge + per-decode-
    # step collective accounting from the compiled HLO (computed once,
    # zeros on single-chip deployments). Per-op byte/count series ride
    # flat so the prometheus exposition stays numeric-only.
    if hasattr(service, "tp_stats"):
        tp = service.tp_stats()
        out["tp_degree"] = int(tp.get("tp_degree", 1))
        out["tp_collective_count_per_step"] = int(
            tp.get("collective_count_per_step", 0))
        out["tp_collective_bytes_per_step"] = int(
            tp.get("collective_bytes_per_step", 0))
        out["tp_collective_floor_bytes"] = int(
            tp.get("analytic_floor_bytes", 0))
        for op, n in (tp.get("counts") or {}).items():
            key = op.replace("-", "_")
            out[f"tp_{key}_count_per_step"] = int(n)
            out[f"tp_{key}_bytes_per_step"] = int(
                (tp.get("bytes") or {}).get(op, 0))
    else:
        out["tp_degree"] = 1
    # health-layer counters (observability/health): anomalies fired,
    # straggler windows flagged, on-demand profiler captures taken
    hc = health_counters()
    out["anomaly_total"] = int(hc["anomaly_total"])
    out["straggler_windows_total"] = int(hc["straggler_windows_total"])
    out["profile_captures_total"] = int(hc["profile_captures_total"])
    # request-tracing layer (ISSUE 8): fixed-bucket latency histograms
    # (TTFT/TPOT/e2e — aggregable fleet-wide by bucket sums, unlike the
    # percentile gauges above) and the SLO breach counters + bounded
    # slow-request-dump count
    hist = getattr(service, "hist", None)
    if hist:
        for k, h in hist.items():
            out[k] = h.snapshot()
    if hasattr(service, "slo_stats"):
        out.update(service.slo_stats())
    # per-request path provenance (ISSUE 18): one flat counter per
    # serve-path fingerprint — the repo's labeled-family convention
    # (the label value rides in the name; fingerprints are [a-z0-9_]
    # by construction, so the series name stays prometheus-legal)
    if hasattr(service, "path_counts_snapshot"):
        for fp, n in sorted(service.path_counts_snapshot().items()):
            out[f"serve_path_{fp}_total"] = int(n)
    # shadow-replay auditor (ISSUE 18): verdict counters + queue gauge,
    # and the per-fingerprint coverage split the fleet dashboard reads
    if auditor is not None:
        out.update(auditor.stats())
        for fp, cov in auditor.coverage().items():
            out[f"audit_path_{fp}_audited_total"] = int(cov["audited"])
            out[f"audit_path_{fp}_divergent_total"] = int(
                cov["divergent"])
    # resilience-supervisor counters (when supervised / a log exists):
    # restarts_total scrapes as a counter; the cause string is JSON-only
    # (prometheus_text emits numeric fields exclusively)
    out.update(supervisor_restart_stats())
    return out


# prometheus_text lives in utils/promtext.py (stdlib-only, below both
# serving tiers — the fleet router emits the same exposition format
# with a pdt_fleet prefix) and stays re-exported here for callers.


class ActiveRequests:
    """In-flight HTTP request gauge: the SIGTERM drain path waits on
    this hitting zero, which (responses complete only after generate()
    returns, SSE included) is exactly "no request mid-generation"."""

    def __init__(self):
        self._n = 0
        self._lock = threading.Lock()

    def __enter__(self):
        with self._lock:
            self._n += 1
        return self

    def __exit__(self, *exc):
        with self._lock:
            self._n -= 1
        return False

    @property
    def count(self) -> int:
        with self._lock:
            return self._n


def make_handler(service: GenerationService, profiler=None,
                 active: ActiveRequests | None = None, tracer=None,
                 auditor=None):
    import itertools

    active = active or ActiveRequests()
    # 1-based STREAMING-request ordinal for the req-unit serving
    # faults (stall_stream@req:N targets THIS process's Nth SSE
    # request — counting streams only keeps the target deterministic
    # under mixed traffic)
    stream_ordinal = itertools.count(1)

    class Handler(BaseHTTPRequestHandler):
        _rid = None   # set per /generate request; echoed on responses

        def _send(self, code: int, payload: dict,
                  headers=()) -> None:
            body = json.dumps(payload).encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if self._rid:
                self.send_header("X-Request-Id", self._rid)
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_text(self, code: int, text: str,
                       content_type: str = "text/plain; version=0.0.4"
                       ) -> None:
            body = text.encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _offer_audit(self, req: dict, out) -> None:
            """Enqueue a finished request for shadow replay (ISSUE
            18). Handler-level on purpose: every scheduler's requests
            funnel through here, so auditing needs no per-engine
            plumbing. offer() never blocks (bounded queue, drops
            counted)."""
            if auditor is None or not isinstance(out, dict):
                return
            if (int(req.get("speculative", 0) or 0)
                    and float(req.get("temperature", 0.0) or 0.0)):
                # sampled speculative decode resamples on rejection —
                # not replayable token-exactly by the plain reference
                # (greedy speculative IS, and stays auditable)
                return
            auditor.offer(audit_record(req, out))

        def do_GET(self):  # noqa: N802 (http.server API)
            with active:
                self._get()

        def _get(self):
            path, _, query = self.path.partition("?")
            if path == "/metrics":
                metrics = service_metrics(service, auditor=auditor)
                if "format=json" in query:
                    return self._send(200, metrics)
                return self._send_text(200, prometheus_text(metrics))
            if path != "/healthz":
                return self._send(404, {"error": "unknown path"})
            # token-integrity verdict (ISSUE 18): a replica whose
            # shadow replay caught a divergence reports "degraded" —
            # still serving (the divergence is sampled evidence, not
            # proof every request is wrong), but the fleet poller
            # surfaces it for rotation instead of routing blind
            degraded = auditor is not None and not auditor.healthy()
            payload = {
                "status": "degraded" if degraded else "ok",
                "arch": service.arch,
                "scheduler": type(service).__name__,
                "vocab_size": service.vocab,
                "tokenizer": service.tokenizer is not None,
                "batching": getattr(service, "stats", None),
                # null until a numerics anomaly fires (health layer)
                "last_anomaly_step": health_counters()[
                    "last_anomaly_step"],
                # resilience supervisor (absent keys = unsupervised)
                **supervisor_restart_stats(),
            }
            if hasattr(service, "latency_percentiles"):
                payload["latency"] = service.latency_percentiles()
            if auditor is not None:
                payload["audit"] = auditor.stats()
            self._send(200, payload)

        def do_POST(self):  # noqa: N802
            with active:
                self._post()

        def _post(self):
            path, _, query = self.path.partition("?")
            if path == "/profile":
                return self._profile(query)
            if path == "/prefill":
                return self._prefill()
            if path == "/export_pages":
                return self._export_pages()
            if path == "/admit_pages":
                return self._admit_pages()
            if path != "/generate":
                return self._send(404, {"error": "unknown path"})
            # request identity (ISSUE 8): honor a propagated
            # X-Request-Id (the fleet router mints one for fleet
            # traffic), mint for direct traffic, echo on EVERY
            # response — a client log line joins server-side spans
            rid = (sanitize_request_id(self.headers.get("X-Request-Id"))
                   or mint_request_id())
            self._rid = rid
            t0 = time.monotonic()
            stream = False
            try:
                # deadline propagation (ISSUE 9): the RELATIVE budget
                # from X-Deadline-Ms, anchored to this hop's receipt
                # (monotonic — clock-skew-free by construction). A
                # malformed value is a client error; an already-spent
                # budget sheds NOW with 504 before any device work.
                try:
                    deadline = Deadline.from_header(
                        self.headers.get(DEADLINE_HEADER), t0=t0)
                except ValueError as e:
                    return self._send(400, {"error": str(e),
                                            "request_id": rid})
                if deadline is not None and deadline.expired():
                    service.stats["deadline_expired"] = (
                        service.stats.get("deadline_expired", 0) + 1)
                    return self._send(
                        504, {"error": "deadline already expired",
                              "request_id": rid},
                        headers=[(DEADLINE_EXPIRED_HEADER, "1")])
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                stream = bool(req.get("stream"))
                if stream:
                    return self._stream(req, rid, deadline=deadline)
                out = _run_request(service, req, request_id=rid,
                                   deadline=deadline)
                out["request_id"] = rid
                # a deadline-truncated result is still a 200 (the
                # budget bought these tokens), but the marker header
                # lets the router classify it OUT of the served SLO
                hdrs = ([(DEADLINE_EXPIRED_HEADER, "1")]
                        if out.get("stop_reason") == "deadline" else [])
                if out.get("serve_path"):
                    # path provenance (ISSUE 18): the fingerprint rides
                    # the response so clients/loadgen join latency to
                    # the path that served them; the router relays it
                    hdrs.append((SERVE_PATH_HEADER,
                                 str(out["serve_path"])))
                self._send(200, out, headers=hdrs)
                self._offer_audit(req, out)
            except DeadlineExceeded as e:
                service.stats["deadline_expired"] = (
                    service.stats.get("deadline_expired", 0) + 1)
                self._send(504, {"error": str(e), "request_id": rid},
                           headers=[(DEADLINE_EXPIRED_HEADER, "1")])
            except ValueError as e:
                self._send(400, {"error": str(e), "request_id": rid})
            except Exception as e:  # surface, don't kill the server
                self._send(500, {"error": f"{type(e).__name__}: {e}",
                                 "request_id": rid})
            finally:
                if tracer is not None:
                    # the replica-side handler span: receive -> last
                    # byte out (SSE tail included) — the stitcher's
                    # "replica" envelope for this request
                    tracer.add(rid, "http", t0, time.monotonic(),
                               stream=stream)
                self._rid = None

        def _prefill(self) -> None:
            """``POST /prefill`` (disaggregated serving, ISSUE 12):
            compute the prompt's KV into this replica's pool and ship
            the full-block chain back as a serialized page payload
            (``application/octet-stream`` — the fleet router relays
            the bytes to a decode replica's ``/admit_pages``). Only
            pages + token ids cross the wire: the decode replica's
            warm admit recomputes the fed suffix window, so output is
            token-identical to a colocated run with no sampling state
            shipped. Prefill- and both-role replicas only."""
            if getattr(service, "role", "both") == "decode":
                return self._send(403, {
                    "error": "decode-role replica: POST pages to "
                             "/admit_pages, prompts to a prefill-role "
                             "replica's /prefill"})
            if not hasattr(service, "prefill_export"):
                return self._send(503, {
                    "error": "scheduler has no prefill export"})
            rid = (sanitize_request_id(self.headers.get("X-Request-Id"))
                   or mint_request_id())
            self._rid = rid
            t0 = time.monotonic()
            try:
                try:
                    deadline = Deadline.from_header(
                        self.headers.get(DEADLINE_HEADER), t0=t0)
                except ValueError as e:
                    return self._send(400, {"error": str(e),
                                            "request_id": rid})
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                payload = service.prefill_export(
                    prompt=req.get("prompt"),
                    prompt_ids=req.get("prompt_ids"),
                    request_id=rid, deadline=deadline)
                body = serialize_pages(payload)
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Length", str(len(body)))
                self.send_header("X-Request-Id", rid)
                self.send_header("X-Ship-Blocks",
                                 str(int(payload["n_blocks"])))
                self.end_headers()
                self.wfile.write(body)
            except DeadlineExceeded as e:
                service.stats["deadline_expired"] = (
                    service.stats.get("deadline_expired", 0) + 1)
                self._send(504, {"error": str(e), "request_id": rid},
                           headers=[(DEADLINE_EXPIRED_HEADER, "1")])
            except ValueError as e:
                self._send(400, {"error": str(e), "request_id": rid})
            except Exception as e:  # surface, don't kill the server
                self._send(500, {"error": f"{type(e).__name__}: {e}",
                                 "request_id": rid})
            finally:
                if tracer is not None:
                    tracer.add(rid, "prefill_http", t0,
                               time.monotonic())
                self._rid = None

        def _export_pages(self) -> None:
            """``POST /export_pages`` (peer page migration, ISSUE
            13): ship whatever full-block chain THIS replica already
            holds for the prompt — resident pages plus checksum-
            verified spilled pages — WITHOUT computing anything
            (contrast ``/prefill``, which computes missing blocks).
            The fleet manager's miss-driven pulls and restart re-warm
            consume it; a replica holding nothing answers
            ``X-Ship-Blocks: 0`` and the puller falls back cold. Any
            role with a pool serves it."""
            if not hasattr(service, "export_cached_pages"):
                return self._send(503, {
                    "error": "scheduler has no page export"})
            rid = (sanitize_request_id(self.headers.get("X-Request-Id"))
                   or mint_request_id())
            self._rid = rid
            t0 = time.monotonic()
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                payload = service.export_cached_pages(
                    prompt=req.get("prompt"),
                    prompt_ids=req.get("prompt_ids"), request_id=rid)
                body = serialize_pages(payload)
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.send_header("Content-Length", str(len(body)))
                self.send_header("X-Request-Id", rid)
                self.send_header("X-Ship-Blocks",
                                 str(int(payload["n_blocks"])))
                self.end_headers()
                self.wfile.write(body)
            except ValueError as e:
                self._send(400, {"error": str(e), "request_id": rid})
            except Exception as e:  # surface, don't kill the server
                self._send(500, {"error": f"{type(e).__name__}: {e}",
                                 "request_id": rid})
            finally:
                if tracer is not None:
                    tracer.add(rid, "export_http", t0,
                               time.monotonic())
                self._rid = None

        def _admit_pages(self) -> None:
            """``POST /admit_pages``: land a shipped page payload
            (serialized ``/prefill`` bytes) in this replica's pool —
            the next ``/generate`` for that prompt admits as a
            zero-recompute block-table pointer update. Decode- and
            both-role replicas only."""
            if getattr(service, "role", "both") == "prefill":
                return self._send(403, {
                    "error": "prefill-role replica does not ingest "
                             "pages (ship them to a decode-role "
                             "replica)"})
            if not hasattr(service, "import_remote_pages"):
                return self._send(503, {
                    "error": "scheduler has no page import"})
            rid = (sanitize_request_id(self.headers.get("X-Request-Id"))
                   or mint_request_id())
            self._rid = rid
            try:
                n = int(self.headers.get("Content-Length", 0))
                # path provenance (ISSUE 18): who pushed these pages —
                # "ship" (disagg prefill handoff, the default) or
                # "pull" (fleet miss-driven peer pull) — tags the
                # adopted radix nodes, so requests that later consume
                # them carry the flag in their serve-path fingerprint
                origin = (self.headers.get("X-Page-Origin")
                          or "ship").strip().lower()
                if origin not in ("ship", "pull"):
                    origin = "ship"
                receipt = service.import_remote_pages(
                    self.rfile.read(n), origin=origin)
                receipt["request_id"] = rid
                self._send(200, receipt)
            except ValueError as e:
                self._send(400, {"error": str(e), "request_id": rid})
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}",
                                 "request_id": rid})
            finally:
                self._rid = None

        def _profile(self, query: str) -> None:
            """``POST /profile?steps=N[&timeout_s=S]``: on-demand
            ``jax.profiler`` capture windowed on the scheduler's own
            progress counters (continuous engine: chunk dispatches;
            static: completed batches/requests) — the serving analogue
            of the trainer's SIGUSR2 step window. Responds after the
            capture closes (steps observed, or timeout on an idle
            server); concurrent captures get 409."""
            if profiler is None:
                return self._send(
                    503, {"error": "profiling not configured"})
            from urllib.parse import parse_qsl

            params = dict(parse_qsl(query))
            try:
                steps = int(params.get("steps", 8))
                timeout_s = float(params.get("timeout_s", 30.0))
            except ValueError as e:
                return self._send(400, {"error": str(e)})

            # ONE monotonic counter per scheduler type — summing
            # overlapping stats (a completed request also advanced
            # 'chunks' for every chunk it consumed; a static batch
            # advances 'batches' AND N x 'requests') would close the
            # window after far fewer scheduler steps than asked. The
            # plain serialized service only counts tokens, so its
            # "step" is a generated token.
            stats = getattr(service, "stats", None) or {}
            counter = next(
                (k for k in ("chunks", "batches", "completed",
                             "requests", "tokens_generated")
                 if k in stats), None)
            if steps > 0 and counter is None:
                return self._send(503, {
                    "error": "scheduler exposes no progress counter; "
                             "use steps=0 for an immediate capture"})

            def progress() -> int:
                s = getattr(service, "stats", None) or {}
                return int(s.get(counter, 0))

            out = profiler.capture(steps=steps, progress_fn=progress,
                                   timeout_s=timeout_s)
            code = (409 if out.get("busy")
                    else 500 if "error" in out else 200)
            self._send(code, out)

        def _stall_stream(self, spec) -> None:
            """The ``stall_stream`` fault: hold the SSE connection
            OPEN without emitting (the nasty middle ground between
            slow and dead — a naive client waits forever). Ends when
            the peer hangs up (the router's deadline-bounded read
            doing its job) or after the spec's duration cap."""
            import select

            deadline = time.monotonic() + max(spec.duration_s, 1.0) \
                * (30.0 if spec.arg is None else 1.0)
            while time.monotonic() < deadline:
                try:
                    r, _, _ = select.select([self.connection], [], [],
                                            0.25)
                    if r and not self.connection.recv(1,
                                                      socket.MSG_PEEK):
                        return           # peer closed: stall is over
                except OSError:
                    return

        def _stream(self, req: dict, rid=None, deadline=None) -> None:
            """Server-sent events: one ``data:`` line per absorbed
            token batch (``{"ids": [...]}``' deltas concatenate to the
            final ids), then a final ``data:`` carrying the complete
            normal response plus ``"done": true``. Delta events carry
            ids only (text would need byte/subword boundary tracking);
            the final event includes ``text`` as usual. On schedulers
            without incremental decode (static groups, speculative)
            one delta covers the whole generation. The response has no
            Content-Length — connection close delimits it (HTTP/1.0
            framing, curl -N friendly)."""
            import queue as queue_mod

            # cheap host-side validation BEFORE committing the 200 SSE
            # response: a bad streaming body must 400 exactly like the
            # identical non-streaming body (ADVICE r5) — once the
            # event-stream headers are out, errors can only arrive as
            # a 200 + error event, which retry logic and load
            # balancers cannot see. Raises ValueError -> _post's
            # handler maps it to 400.
            service.validate_request(req)
            # stall_stream fault (ISSUE 9): armed for this process's
            # Nth streaming request — after the first delta the stream
            # freezes WITHOUT closing
            stall_spec = faults.on_serve_request(next(stream_ordinal))

            q: "queue_mod.Queue" = queue_mod.Queue()
            out: dict = {}

            incremental = getattr(service, "STREAM_DELTAS", False)
            # speculative requests bypass the slot engine (batch-1
            # under the lock) and don't honor mid-flight cancel
            can_cancel = incremental and not int(
                req.get("speculative", 0))
            cancel_evt = threading.Event() if can_cancel else None

            def run():
                try:
                    r = _run_request(
                        service, req,
                        on_tokens=(lambda ids: q.put(("tokens", ids)))
                        if incremental else None,
                        cancel=cancel_evt, request_id=rid,
                        deadline=deadline)
                    if rid:
                        r["request_id"] = rid
                    out["r"] = r
                    if not incremental and r.get("ids"):
                        q.put(("tokens", r["ids"]))  # one final delta
                    q.put(("done", None))
                except Exception as e:  # noqa: BLE001 — surfaced below
                    q.put(("error", e))

            threading.Thread(target=run, daemon=True).start()
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            if rid:
                self.send_header("X-Request-Id", rid)
            self.end_headers()

            def emit(payload: dict) -> None:
                self.wfile.write(
                    b"data: " + json.dumps(payload).encode("utf-8")
                    + b"\n\n")
                self.wfile.flush()

            # headers are out: from here NOTHING may write a second
            # HTTP response onto this connection. A client that
            # disconnects mid-stream raises on emit — swallow it, and
            # on the slot engine CANCEL the generation so its slot
            # frees at the next chunk absorb instead of decoding out
            # the remaining budget.
            try:
                while True:
                    kind, payload = q.get()
                    if kind == "tokens":
                        emit({"ids": [int(t) for t in payload]})
                        if stall_spec is not None:
                            # the stream freezes here, connection
                            # open: the router's deadline-bounded
                            # upstream read is what frees the client.
                            # Cancel the generation so the slot
                            # recycles; the worker's queued events
                            # are simply never read.
                            self._stall_stream(stall_spec)
                            if cancel_evt is not None:
                                cancel_evt.set()
                            return
                    elif kind == "error":
                        e = payload
                        emit({"error": f"{type(e).__name__}: {e}",
                              "done": True})
                        return
                    else:
                        emit({**out["r"], "done": True})
                        # streamed completions audit too — serve_path
                        # rode the result dict into the done event
                        self._offer_audit(req, out["r"])
                        return
            except (BrokenPipeError, ConnectionError, OSError):
                if cancel_evt is not None:
                    cancel_evt.set()
                return

        def log_message(self, fmt, *fmt_args):
            pass  # suppress http.server's noisy per-request stderr lines

    return Handler


def main(args, config):
    logger = config.get_logger("serve")
    # validate --warm-buckets BEFORE the (expensive) checkpoint restore:
    # a typo should fail in milliseconds, not after a multi-GB load
    try:
        warm_buckets = [int(b) for b in args.warm_buckets.split(",")
                        if b.strip()]
    except ValueError:
        raise SystemExit(
            f"--warm-buckets must be comma-separated integers, got "
            f"{args.warm_buckets!r}")
    # persistent compile cache BEFORE any executable builds: a restarted
    # server re-reads its warmup ladder from disk instead of recompiling
    configure_compile_cache(config)
    # DP×TP geometry (ISSUE 12): --dp N runs N independent tp-chip
    # engine groups in THIS process (engine/dp.py); validated before
    # any load so a geometry typo fails in milliseconds
    dp = max(int(args.dp), 1)
    tsdb = None      # set by the schedulers that feed one (below)
    if dp > 1:
        from pytorch_distributed_template_tpu.parallel.tp import (
            validate_dp_geometry,
        )

        validate_dp_geometry(dp, max(int(args.tp), 1))
        if args.scheduler not in ("auto", "continuous"):
            raise SystemExit(
                "--dp > 1 requires the continuous scheduler "
                f"(got --scheduler {args.scheduler})")
        model = params = tok = probe = None
    else:
        model, params, tok = load_generation_stack(
            config, use_ema=args.ema, tensor_parallel=args.tp)
        probe = GenerationService.from_model(model, params, tok)
    # serving.prefix_cache config block (paged KV block pool + radix
    # prefix index, engine/kvcache.py) with CLI override: --prefix-cache
    # on forces it even without a config block, off disables one
    prefix_cfg = dict((config.get("serving") or {}).get(
        "prefix_cache") or {})
    if args.prefix_cache == "on":
        prefix_cfg["enabled"] = True
    elif args.prefix_cache == "off":
        prefix_cfg["enabled"] = False
    # tiered spill hierarchy (ISSUE 13): CLI wins over the config
    # block; 0 / empty keeps destroy-on-evict
    if args.spill_blocks > 0:
        prefix_cfg["host_spill_blocks"] = args.spill_blocks
    if args.spill_dir:
        prefix_cfg["disk_spill_dir"] = args.spill_dir
        if args.spill_disk_blocks > 0:
            prefix_cfg["disk_spill_blocks"] = args.spill_disk_blocks
    # chunked streaming prefill (ISSUE 15): CLI wins over the config's
    # serving.prefill_chunk_tokens; the knob also sizes the ring slack
    # for sliding-window pools (the two must agree, so it rides the
    # prefix_cfg dict the pool reads)
    prefill_chunk = int(args.prefill_chunk_tokens or 0) or int(
        (config.get("serving") or {}).get("prefill_chunk_tokens") or 0)
    if prefill_chunk:
        prefix_cfg["prefill_chunk_tokens"] = prefill_chunk
    if args.role != "both" and not prefix_cfg.get("enabled"):
        # role-split serving IS page shipping: refuse the geometry in
        # milliseconds instead of deep in service construction
        raise SystemExit(
            f"--role {args.role} requires the prefix cache "
            "(--prefix-cache on or a serving.prefix_cache config "
            "block): page shipping moves pool pages")
    # early-exit draft depth for speculative requests (ISSUE 7): the
    # model's own first k blocks + head draft, sharing the target's
    # cache and the prefix pool's warm blocks (engine/generate
    # draft_layers); 0 keeps n-gram prompt lookup
    spec_draft_layers = int((config.get("serving") or {}).get(
        "speculative_draft_layers") or 0)
    # request-scoped tracing + SLO layer (ISSUE 8): the tracer appends
    # this process's request-keyed spans to <save_dir>/spans.jsonl
    # (scripts/trace_stitch.py merges them with the router's into one
    # cross-process timeline); the SLO watcher turns configured
    # TTFT/e2e thresholds into slo_breach_total on /metrics + bounded
    # slow_request_<rid>.json dumps. Thresholds: CLI wins, else the
    # config's serving.slo block; no thresholds = counters stay 0.
    tracer = None
    if args.reqtrace != "off":
        tracer = RequestTracer(config.save_dir / "spans.jsonl",
                               process="serve")
    # brownout ladder (ISSUE 9): ordered degradation under overload
    # (disable spec -> short chunks -> clamp budgets), driven by queue
    # depth / pool occupancy / SLO breach rate with hysteresis.
    # Config serving.brownout block; --brownout on/off overrides; the
    # threshold flags override the config's knobs. Off by default —
    # level 3 clamps budgets, which an operator must opt into.
    brownout_cfg = dict((config.get("serving") or {}).get(
        "brownout") or {})
    if args.brownout == "on":
        brownout_cfg["enabled"] = True
    elif args.brownout == "off":
        brownout_cfg["enabled"] = False
    if args.brownout_queue_norm > 0:
        brownout_cfg["queue_norm"] = args.brownout_queue_norm
    if args.brownout_dwell_s > 0:
        brownout_cfg["dwell_s"] = args.brownout_dwell_s
    if args.brownout_max_new > 0:
        brownout_cfg["max_new_cap"] = args.brownout_max_new
    slo_cfg = dict((config.get("serving") or {}).get("slo") or {})
    slo = SloWatcher(
        ttft_s=(args.slo_ttft_s or slo_cfg.get("ttft_s")),
        e2e_s=(args.slo_e2e_s or slo_cfg.get("e2e_s")),
        dump_dir=config.save_dir, tracer=tracer,
        max_dumps=int(slo_cfg.get("max_dumps", 8)),
        cooldown_s=float(slo_cfg.get("cooldown_s", 30.0)))
    want = args.scheduler
    if dp > 1:
        want = "dp"
    elif want == "auto":
        # sliding-window models (ISSUE 15): not pad-capable (rolling
        # contiguous cache), but the paged RING layout serves them on
        # the continuous engine when a pool is configured
        ring_ok = (int(getattr(model, "window", 0) or 0) > 0
                   and bool(prefix_cfg.get("enabled")))
        want = ("continuous"
                if (probe._pad_ok or ring_ok) and args.max_batch > 1
                else "static" if args.max_batch > 1 else "none")
    if want == "dp":
        # DP×TP (ISSUE 12): N independent continuous engines, one per
        # tp-chip group, behind one cache-aware facade (engine/dp.py).
        # The recorder belongs to group 0 alone — the per-chunk JSONL's
        # "last record wins" analyzer contract cannot survive N
        # engines interleaving cumulative counters in one file.
        from pytorch_distributed_template_tpu.engine.dp import (
            DataParallelService,
        )
        from pytorch_distributed_template_tpu.observability.telemetry \
            import FlightRecorder

        recorder = FlightRecorder(run_dir=str(config.save_dir),
                                  memory_every=0)
        # fleet timeline store (ISSUE 14): group 0 alone feeds it,
        # same single-writer contract as the recorder's JSONL
        tsdb = TimeSeriesStore(config.save_dir / "timeseries.jsonl",
                               process="serve")
        set_default_store(tsdb)
        service = DataParallelService.build_from_config(
            config, ContinuousBatchingService, use_ema=args.ema,
            dp=dp, tp=max(int(args.tp), 1),
            service_kw=dict(
                slots=args.max_batch, chunk=args.decode_chunk,
                window_ms=args.batch_window_ms,
                warm_buckets=warm_buckets, prefix_cache=prefix_cfg,
                spec_draft_layers=spec_draft_layers, tracer=tracer,
                slo=slo, brownout=brownout_cfg, role=args.role,
                prefill_chunk_tokens=prefill_chunk),
            service_kw_fn=lambda g: ({"recorder": recorder,
                                      "tsdb": tsdb}
                                     if g == 0 else {}),
        )
    elif want == "continuous":
        # slot scheduler: rows admit/free mid-flight, no group keys
        # (engine/continuous.py); RoPE + non-rolling-cache models only.
        # Per-chunk serving telemetry (FlightRecorder JSONL next to the
        # run's logs — scripts/telemetry_report.py renders the prefix-
        # cache section from it): built HERE, not unconditionally — the
        # other schedulers never record, and an unused recorder would
        # leave an open JSONL handle + atexit registration behind
        from pytorch_distributed_template_tpu.observability.telemetry \
            import FlightRecorder

        recorder = FlightRecorder(run_dir=str(config.save_dir),
                                  memory_every=0)
        # fleet timeline store (ISSUE 14): per-chunk counters fold
        # into fixed-interval rate points in timeseries.jsonl; also
        # the process default, so watchdog/anomaly dumps carry the
        # trend window
        tsdb = TimeSeriesStore(config.save_dir / "timeseries.jsonl",
                               process="serve")
        set_default_store(tsdb)
        service = ContinuousBatchingService.from_model(
            model, params, tok, slots=args.max_batch,
            chunk=args.decode_chunk, window_ms=args.batch_window_ms,
            warm_buckets=warm_buckets, prefix_cache=prefix_cfg,
            recorder=recorder, spec_draft_layers=spec_draft_layers,
            tracer=tracer, slo=slo, brownout=brownout_cfg,
            role=args.role, tsdb=tsdb,
            prefill_chunk_tokens=prefill_chunk,
        )
    elif want == "static":
        # the static micro-batch scheduler's shared-group prefill does
        # not consult the pool (group members already share one
        # prefill); prefix caching rides the continuous/plain paths —
        # and role-split serving IS the pool, so it rides them too
        if args.role != "both":
            raise SystemExit(
                "--role prefill|decode needs a prefix-cache-capable "
                "scheduler (continuous or none), not static")
        service = BatchedGenerationService.from_model(
            model, params, tok, max_batch=args.max_batch,
            window_ms=args.batch_window_ms,
            spec_draft_layers=spec_draft_layers,
            tracer=tracer, slo=slo,
        )
    else:  # plain serialized service — rebuilt so the pool/tracer
        # attach (from_model on loaded params is cheap; the probe has
        # neither)
        service = GenerationService.from_model(
            model, params, tok, prefix_cache=prefix_cfg,
            spec_draft_layers=spec_draft_layers,
            tracer=tracer, slo=slo, role=args.role)
    logger.info("scheduler: %s", type(service).__name__)
    # sampled shadow-replay token-integrity auditor (ISSUE 18): replay
    # completed requests through a cold reference sharing THE serving
    # model/params and compare token ids exactly. Default reference is
    # the no-pool probe (exact for f32/bf16 pools and ring layouts —
    # the contiguous rolling cache is gated token-identical to the
    # paged ring); an int8-KV pool instead gets a reference with its
    # OWN private pool, because pool pages and the contiguous cache
    # quantize at different granularities — an int8 no-pool replay
    # would false-positive on healthy traffic (tests/test_audit.py
    # pins the discipline). Config serving.audit block; --audit
    # on/off overrides.
    audit_cfg = dict((config.get("serving") or {}).get("audit") or {})
    if args.audit == "on":
        audit_cfg["enabled"] = True
    elif args.audit == "off":
        audit_cfg["enabled"] = False
    if args.audit_sample_rate > 0:
        audit_cfg["sample_rate"] = args.audit_sample_rate
    if args.audit_floor > 0:
        audit_cfg["floor"] = args.audit_floor
    auditor = None
    if audit_cfg.get("enabled"):
        if probe is None:
            # dp>1 loads per-group models inside the facade; there is
            # no single-model reference to replay through (yet)
            logger.warning("audit: unavailable with --dp > 1; "
                           "disabled")
        else:
            ref_service = probe
            kvq = str(getattr(model, "kv_quant", "") or "")
            if kvq and (prefix_cfg or {}).get("enabled"):
                # like-for-like: cold through the same quantized pool
                # layout, in a pool of its own (never shares serving
                # pages — a corrupted serving page must not leak into
                # its own reference)
                ref_service = GenerationService.from_model(
                    model, params, tok,
                    prefix_cache=dict(prefix_cfg))
                logger.info("audit: pooled %s reference (like-for-"
                            "like quantized replay)", kvq)

            def _reference(rec: dict):
                resp = ref_service.generate(
                    prompt=rec.get("prompt"),
                    prompt_ids=rec.get("prompt_ids"),
                    max_new_tokens=int(rec.get("max_new_tokens", 64)),
                    temperature=float(rec.get("temperature", 0.0)),
                    top_k=int(rec.get("top_k", 0)),
                    top_p=float(rec.get("top_p", 0.0)),
                    seed=int(rec.get("seed", 0)),
                    stop=rec.get("stop"))
                return resp.get("ids") or []

            auditor = ShadowAuditor(
                _reference,
                sample_rate=float(audit_cfg.get("sample_rate", 0.05)),
                floor=int(audit_cfg.get("floor", 4)),
                queue_max=int(audit_cfg.get("queue_max", 64)),
                dump_dir=config.save_dir, tracer=tracer, tsdb=tsdb)
            logger.info(
                "audit: shadow replay on (sample_rate=%.3f floor=%d)",
                auditor.sample_rate, auditor.floor)
    # on-demand profiling (POST /profile): captures land next to the
    # serving run's logs
    profiler = OnDemandProfiler(config.save_dir)
    active = ActiveRequests()
    server = ThreadingHTTPServer(
        (args.host, args.port),
        make_handler(service, profiler=profiler, active=active,
                     tracer=tracer, auditor=auditor)
    )
    # drain on SIGTERM (the preemption path, same contract as the
    # trainer's): stop accepting, let in-flight requests finish
    # (bounded by --drain-grace-s), exit EXIT_PREEMPTED so a
    # supervising fleet (scripts/serve_fleet.py) classifies the stop
    # as a budget-free preemption — a rolling restart costs zero
    # failed requests
    draining = threading.Event()

    def _on_sigterm(signum, frame):  # noqa: ARG001
        if draining.is_set():
            return
        draining.set()
        # shutdown() blocks until serve_forever exits, and this
        # handler runs ON the serve_forever thread — do it elsewhere
        threading.Thread(target=server.shutdown, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded use)
    logger.info(
        "serving %s (vocab %d%s) on http://%s:%d — POST /generate, "
        "GET /healthz", service.arch, service.vocab,
        ", tokenizer" if service.tokenizer else "",
        args.host, server.server_address[1],
    )
    print(f"READY http://{args.host}:{server.server_address[1]}",
          flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    if auditor is not None:
        # stop feeding the replay worker; queued audits are abandoned
        # (a draining replica's verdicts already rode /metrics)
        auditor.close()
    if draining.is_set():
        deadline = time.monotonic() + args.drain_grace_s
        while active.count and time.monotonic() < deadline:
            time.sleep(0.05)
        server.server_close()
        if tsdb is not None:
            # emit the open interval before exit: a short-lived
            # replica's trend must not evaporate with the drain
            tsdb.close()
        logger.info("drained (%d request(s) still open); exiting via "
                    "the preemption path", active.count)
        sys.exit(EXIT_PREEMPTED)
    if tsdb is not None:
        tsdb.close()      # Ctrl-C / embedded exit path, same contract


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="LM HTTP serving CLI")
    parser.add_argument("-c", "--config", default=None, type=str)
    parser.add_argument("-r", "--resume", required=True, type=str,
                        help="Checkpoint or serving artifact to serve.")
    parser.add_argument("-s", "--save_dir", default=None, type=str)
    parser.add_argument("--host", default="127.0.0.1", type=str)
    parser.add_argument("--port", default=8000, type=int,
                        help="0 picks a free port (printed on READY).")
    parser.add_argument("--ema", action="store_true")
    parser.add_argument("--max-batch", default=8, type=int,
                        help="scheduler width (slots); 1 disables "
                             "batching")
    parser.add_argument("--batch-window-ms", default=25.0, type=float,
                        help="how long the scheduler waits to group "
                             "concurrent compatible requests")
    parser.add_argument("--scheduler", default="auto",
                        choices=("auto", "continuous", "static", "none"),
                        help="auto = continuous batching (slot-based, "
                             "no group keys) on RoPE/non-rolling "
                             "models, static micro-batching otherwise")
    parser.add_argument("--warm-buckets", default="", type=str,
                        metavar="N,N,...",
                        help="continuous scheduler: prompt-length "
                             "buckets whose admission executables "
                             "compile at STARTUP (with the chunk "
                             "ladder) instead of at the first arrival "
                             "wave — e.g. 64,128,256 for chat traffic; "
                             "empty disables (default). Pairs with "
                             "compile_cache: a restarted server reads "
                             "the whole ladder from disk")
    parser.add_argument("--tp", default=0, type=int,
                        help="tensor-parallel serving degree (ISSUE "
                             "10): shard weights + the paged KV pool "
                             "over a {'tensor': tp} mesh so decode "
                             "runs as one SPMD program. 0 follows the "
                             "config's serving.tensor_parallel "
                             "(default 1 = single chip); geometry that "
                             "cannot shard refuses at startup. On CPU "
                             "dev boxes pair with XLA_FLAGS="
                             "--xla_force_host_platform_device_count=N")
    parser.add_argument("--role", default="both",
                        choices=("both", "prefill", "decode"),
                        help="disaggregated serving role (ISSUE 12): "
                             "'prefill' computes prompt KV and SHIPS "
                             "pool pages via POST /prefill (refuses "
                             "decode-scale budgets); 'decode' ingests "
                             "shipped pages via POST /admit_pages and "
                             "serves decode; 'both' (default) is the "
                             "classic colocated replica. Role-split "
                             "replicas require the prefix cache")
    parser.add_argument("--dp", default=1, type=int,
                        help="data-parallel group count (ISSUE 12): "
                             "run N independent --tp-chip engine "
                             "groups in this process behind one "
                             "cache-aware facade — needs dp x tp "
                             "local devices; continuous scheduler "
                             "only")
    parser.add_argument("--prefix-cache", default="auto",
                        choices=("auto", "on", "off"),
                        help="paged KV prefix cache (engine/kvcache.py)"
                             ": auto follows the config's "
                             "serving.prefix_cache block; on/off "
                             "override it. Shared prompt prefixes "
                             "(system / few-shot preambles) admit as "
                             "an HBM block copy + suffix-only prefill "
                             "instead of a full recompute")
    parser.add_argument("--prefill-chunk-tokens", default=0, type=int,
                        help="chunked streaming prefill (ISSUE 15): "
                             "prompts whose uncached suffix exceeds "
                             "this many tokens admit incrementally "
                             "across scheduler ticks (power of two; "
                             "0 = config serving.prefill_chunk_tokens, "
                             "else monolithic admits — window models "
                             "default to the ring slack)")
    parser.add_argument("--spill-blocks", default=0, type=int,
                        help="host-RAM KV spill tier size in blocks "
                             "(ISSUE 13): eviction DEMOTES page bytes "
                             "(sha256-checksummed) instead of "
                             "destroying them, and a radix hit on a "
                             "spilled chain promotes it back. 0 (or "
                             "no serving.prefix_cache."
                             "host_spill_blocks) keeps classic "
                             "destroy-on-evict")
    parser.add_argument("--spill-dir", default="", type=str,
                        help="disk KV spill tier directory: host-tier "
                             "overflow demotes here instead of being "
                             "dropped (checksums verified on every "
                             "read); empty disables the disk tier")
    parser.add_argument("--spill-disk-blocks", default=256, type=int,
                        help="disk spill tier size in blocks "
                             "(with --spill-dir)")
    parser.add_argument("--reqtrace", default="on",
                        choices=("on", "off"),
                        help="request-scoped span tracing "
                             "(observability/reqtrace.py): appends "
                             "X-Request-Id-keyed spans to "
                             "<save_dir>/spans.jsonl for the "
                             "cross-process stitcher "
                             "(scripts/trace_stitch.py)")
    parser.add_argument("--slo-ttft-s", default=0.0, type=float,
                        help="TTFT SLO threshold in seconds: breaches "
                             "bump slo_breach_total on /metrics and "
                             "write bounded slow_request_<rid>.json "
                             "dumps (0 = use config serving.slo, else "
                             "off)")
    parser.add_argument("--slo-e2e-s", default=0.0, type=float,
                        help="end-to-end latency SLO threshold in "
                             "seconds (0 = use config serving.slo, "
                             "else off)")
    parser.add_argument("--brownout", default="auto",
                        choices=("auto", "on", "off"),
                        help="brownout ladder (ISSUE 9): ordered "
                             "degradation under overload — disable "
                             "speculative decode, cap chunk growth, "
                             "clamp admitted budgets — with "
                             "hysteresis. auto follows the config's "
                             "serving.brownout block (off when "
                             "absent); level is a /metrics gauge")
    parser.add_argument("--brownout-queue-norm", default=0.0,
                        type=float,
                        help="queue depth equal to slots x this reads "
                             "as pressure 1.0 (0 = config/default 1.0)")
    parser.add_argument("--brownout-dwell-s", default=0.0, type=float,
                        help="minimum seconds at a brownout level "
                             "before it may step back down (0 = "
                             "config/default 2.0)")
    parser.add_argument("--brownout-max-new", default=0, type=int,
                        help="level-3 cap on admitted max_new_tokens "
                             "(0 = config/default 4x decode chunk)")
    parser.add_argument("--audit", default="auto",
                        choices=("auto", "on", "off"),
                        help="sampled shadow-replay token-integrity "
                             "auditing (ISSUE 18): completed requests "
                             "are sampled (stratified by serve-path "
                             "fingerprint) and replayed through the "
                             "cold no-pool reference on a background "
                             "worker; any token mismatch bumps "
                             "token_divergence_total, writes a "
                             "bounded divergence_<rid>.json bundle "
                             "and degrades /healthz. auto follows the "
                             "config's serving.audit block (off when "
                             "absent); needs --dp 1")
    parser.add_argument("--audit-sample-rate", default=0.0, type=float,
                        help="post-floor audited fraction per "
                             "fingerprint (0 = config serving.audit."
                             "sample_rate, default 0.05)")
    parser.add_argument("--audit-floor", default=0, type=int,
                        help="per-fingerprint coverage floor: the "
                             "first N completions of EVERY fingerprint "
                             "audit regardless of sample rate, so rare "
                             "paths stay covered (0 = config, "
                             "default 4)")
    parser.add_argument("--drain-grace-s", default=30.0, type=float,
                        help="SIGTERM drain: how long to wait for "
                             "in-flight requests to finish before "
                             "exiting (preemption path, rc 75)")
    parser.add_argument("--decode-chunk", default=8, type=int,
                        help="continuous scheduler: BASE decode steps "
                             "per dispatch (admission latency bound); "
                             "when every slot is busy the engine grows "
                             "chunks toward the shortest remaining "
                             "budget, so a small base costs saturated "
                             "throughput nothing")
    args, config = ConfigParser.from_args(parser, (), training=False)
    main(args, config)
