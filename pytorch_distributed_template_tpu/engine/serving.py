"""Shared serving-side loading: checkpoint/artifact -> (model, params).

The one place that knows how to turn ``config.resume`` into something
``generate()`` can run, used by both front-ends (the one-shot
``generate.py`` CLI and the ``serve.py`` HTTP server):

- a TRAINING checkpoint restores through the full TrainState template
  (optimizer slots and all — engine/evaluator.restore_template_state),
  honoring ``use_ema``;
- a params-only SERVING artifact (scripts/quantize_checkpoint.py,
  scripts/merge_lora.py) restores just the param tree, sharded over the
  mesh per the model's partition rules (multi-host-legal);
- the run's BPE tokenizer, when the experiment trained through
  ``BpeLMLoader``, rides along for text round-tripping.
"""
from __future__ import annotations

import logging

import jax

from ..checkpoint import load_serving_meta, restore_serving_params
from ..config.registry import MODELS
from ..data.tokenizer import tokenizer_from_config
from ..models.base import inject_mesh
from ..parallel import apply_rules, dist, mesh_from_config
from .evaluator import restore_template_state

logger = logging.getLogger(__name__)


class DeadlineExceeded(RuntimeError):
    """A request's ``X-Deadline-Ms`` budget expired before (or while)
    it could be served (ISSUE 9). serve.py maps this to HTTP 504 with
    the ``X-Deadline-Expired`` marker header; the continuous engine
    never raises it mid-flight (an expired decoding row finalizes
    with its partial tokens and ``stop_reason: "deadline"`` instead —
    truncation beats throwing work away)."""


class GenerationService:
    """The request-level generation entry shared by BOTH front-ends
    (generate.py one-shot CLI, serve.py HTTP server): prompt encoding +
    validation, speculative-vs-sampled dispatch, and text/ids decoding
    live HERE once — a fix in one front-end cannot miss the other.

    ``generate`` is serialized with a lock: one chip, one compiled
    decode path (harmless for the one-shot CLI, load-bearing for the
    threaded HTTP server).
    """

    def __init__(self, config, use_ema: bool = False,
                 tensor_parallel: int = 0, **kw):
        model, params, tokenizer = load_generation_stack(
            config, use_ema=use_ema, tensor_parallel=tensor_parallel
        )
        self._setup(model, params, tokenizer, **kw)

    @classmethod
    def from_model(cls, model, params, tokenizer=None, **kw):
        """Build a service around an already-loaded (model, params) —
        the bench rungs and scheduler tests construct services this
        way instead of going through checkpoint restore."""
        obj = cls.__new__(cls)
        obj._setup(model, params, tokenizer, **kw)
        return obj

    #: serving roles (disaggregated prefill/decode, ISSUE 12): a
    #: "prefill" replica computes prompt KV into its pool and SHIPS the
    #: pages (``prefill_export``) — it refuses decode-scale budgets; a
    #: "decode" replica ingests shipped pages (``import_remote_pages``)
    #: and serves decode; "both" (default) is the classic colocated
    #: replica, byte-identical to the pre-disaggregation stack.
    ROLES = ("both", "prefill", "decode")
    #: the largest budget a prefill-role replica serves on /generate:
    #: 1 token = prefill + first sample (health pokes, manual tests);
    #: anything longer is decode work the router mis-routed
    PREFILL_MAX_NEW = 1

    def _setup(self, model, params, tokenizer=None, prefix_cache=None,
               spec_draft_layers: int = 0, tracer=None, slo=None,
               role: str = "both"):
        import inspect
        import threading

        from ..utils.promtext import LatencyHistogram

        from ..parallel.tp import tp_degree

        self.model, self.params, self.tokenizer = model, params, tokenizer
        self.vocab = int(getattr(self.model, "vocab_size", 0))
        self.arch = type(self.model).__name__
        if role not in self.ROLES:
            raise ValueError(f"unknown serving role {role!r} "
                             f"(one of {self.ROLES})")
        self.role = role
        # TP serving (ISSUE 10): the mesh rides on the model
        # (load_generation_stack injects it); tp=1 keeps every path
        # byte-identical to the single-chip stack
        self._mesh = getattr(model, "mesh", None)
        self.tp = tp_degree(self._mesh)
        self._tp_stats = None
        self._tp_stats_lock = threading.Lock()
        # pad-capable = the model supports per-row left-pad masking
        # (RoPE families, non-rolling cache): enables mixed-length
        # micro-batching and length-bucketed speculative executables
        self._pad_ok = (
            "pad_lens" in inspect.signature(
                type(self.model).__call__).parameters
            and int(getattr(self.model, "window", 0) or 0) == 0
        )
        self._lock = threading.Lock()
        # batched prefill export (ISSUE 13 satellite — PR 12 documented
        # its batch-1-under-the-lock contract as the honest follow-on):
        # concurrent /prefill callers enqueue their chains and ONE
        # leader thread drains the queue under a single service-lock
        # acquisition, so a handoff burst queues behind one lock wait
        # instead of N of them
        self._export_mu = threading.Lock()       # guards the queue
        self._export_leader = threading.Lock()   # one processor
        self._export_q: list = []
        # paged KV prefix cache (engine/kvcache.py): either a prebuilt
        # PrefixCache or a ``serving.prefix_cache`` config dict. A
        # layout that cannot pool (rolling window, int8 KV, no
        # kv_cache_spec) disables LOUDLY instead of failing the load —
        # the operator asked for a server, not a cache
        self._prefix = None
        # pool-fallback observability (ISSUE 15 satellite): when the
        # pool REFUSES to construct, the machine-readable reason
        # (window / kv_quant / undersized / gpt2_layout) survives here
        # so /metrics can count the degradation instead of burying the
        # refusal string in logs
        self.pool_refusal_reason = ""
        if prefix_cache is not None:
            from .kvcache import PrefixCache

            if isinstance(prefix_cache, PrefixCache):
                self._prefix = prefix_cache
            elif dict(prefix_cache).get("enabled"):
                cfg = dict(prefix_cache)
                try:
                    self._prefix = PrefixCache(
                        model, params,
                        block_tokens=int(cfg.get("block_tokens", 32)),
                        pool_blocks=int(cfg.get("pool_blocks", 256)),
                        eviction=cfg.get("eviction", "lru"),
                        paged=bool(cfg.get("paged", True)),
                        # tiered spill hierarchy (ISSUE 13): 0 / None
                        # keeps the classic destroy-on-evict pool
                        host_spill_blocks=int(
                            cfg.get("host_spill_blocks", 0)),
                        disk_spill_dir=cfg.get("disk_spill_dir"),
                        disk_spill_blocks=int(
                            cfg.get("disk_spill_blocks", 0)),
                        # sliding-window ring geometry (ISSUE 15): the
                        # largest single prefill feed the ring must
                        # tolerate; chunked prefill keeps feeds inside
                        ring_slack_tokens=int(
                            cfg.get("prefill_chunk_tokens", 0)
                            or cfg.get("ring_slack_tokens", 512)),
                    )
                except ValueError as e:
                    logger.warning("prefix cache disabled: %s", e)
                    self.pool_refusal_reason = getattr(
                        e, "reason", "unsupported")
        if self.role != "both" and self._prefix is None:
            # role-split serving IS page shipping: a prefill replica
            # with no pool has nothing to export, and a decode replica
            # with no pool has nowhere to land an import — refuse at
            # startup, not at the first handoff
            raise ValueError(
                f"role={self.role!r} needs a prefix cache "
                "(serving.prefix_cache.enabled / --prefix-cache on): "
                "page shipping moves pool pages")
        # early-exit draft depth for speculative requests (ISSUE 7):
        # 0 keeps the n-gram prompt-lookup drafter; > 0 drafts with the
        # model's own first k blocks + head (engine/generate
        # ``draft_layers``), sharing the target's cache and the prefix
        # pool's warm blocks
        self._spec_draft_layers = int(spec_draft_layers)
        if self._spec_draft_layers and (
                "exit_layer" not in inspect.signature(
                    type(model).__call__).parameters
                or not (0 < self._spec_draft_layers
                        < int(getattr(model, "n_layer", 0)))):
            logger.warning(
                "speculative_draft_layers=%d unusable for %s (needs "
                "exit_layer support and 0 < k < n_layer): falling back "
                "to n-gram drafting", self._spec_draft_layers,
                type(model).__name__)
            self._spec_draft_layers = 0
        # request-scoped tracing + SLO plumbing (ISSUE 8,
        # observability/reqtrace.py): the tracer appends request-keyed
        # spans to this process's spans.jsonl, the SLO watcher turns
        # per-request TTFT/e2e into breach counters + bounded
        # slow-request dumps. Both optional (None = zero overhead);
        # serve.py passes them in, library/test use stays untouched.
        self._tracer = tracer
        self._slo = slo
        # fixed-bucket Prometheus histograms (utils/promtext): TTFT and
        # TPOT fill only on schedulers that observe first-token time
        # (the continuous engine); e2e fills everywhere — the fleet
        # poller SUMS these bucket counters into aggregable
        # fleet-level latency (averaging percentile gauges is not
        # aggregation)
        self.hist = {"ttft_seconds": LatencyHistogram(),
                     "tpot_seconds": LatencyHistogram(),
                     "e2e_seconds": LatencyHistogram()}
        # per-request serve-path provenance (ISSUE 18): fingerprint ->
        # served-request count, rendered by serve.py's /metrics as the
        # serve_path_<fingerprint>_total counter family
        self._path_counts: dict = {}
        self._path_lock = threading.Lock()
        # scheduler subclasses overwrite this with richer dicts in
        # their own _setup (after this super() call); the plain
        # serialized service still exposes a token counter for /metrics
        self.stats = {"tokens_generated": 0}

    def _observe_request(self, request_id, t0: float, resp: dict,
                         ttft_s=None) -> None:
        """One completed request's latency bookkeeping for schedulers
        WITHOUT their own engine-side observation point (plain and
        static paths; the continuous engine observes in ``_complete``
        where TTFT and token counts are known): e2e histogram, SLO
        check, and the tracer's ``complete`` event."""
        import time

        e2e = time.monotonic() - t0
        self.hist["e2e_seconds"].observe(e2e)
        tokens = len(resp.get("ids") or ())
        if self._tracer is not None and request_id:
            self._tracer.event(request_id, "complete",
                               e2e_s=round(e2e, 6), tokens=tokens,
                               stop_reason=resp.get("stop_reason"))
        if self._slo is not None and request_id:
            self._slo.observe(request_id, ttft_s=ttft_s, e2e_s=e2e,
                              tokens=tokens)

    def _base_path(self, speculative: int = 0) -> dict:
        """The request-independent half of a serve-path fingerprint
        (ISSUE 18): kv layout + TP geometry + spec intent. Engines add
        the admit mode and the pool events the request consumed before
        :meth:`_finalize_path` renders it."""
        pf = getattr(self, "_prefix", None)
        kvq = str(getattr(pf, "kv_quant", "")
                  or getattr(self.model, "kv_quant", "") or "")
        window = int(getattr(pf, "window", 0)
                     or getattr(self.model, "window", 0) or 0)
        return {"mode": "cold", "tp": self.tp,
                "int8": kvq == "int8", "ring": window > 0,
                "spec": int(speculative) > 0}

    def _finalize_path(self, resp: dict, path: dict,
                       request_id=None) -> str:
        """Render ``path`` to its fingerprint and attach it everywhere
        a completed request is observable: the wire response
        (``serve_path`` — serve.py echoes it as ``X-Serve-Path``), the
        per-fingerprint request counters, and the request's trace."""
        from ..observability.reqtrace import path_fingerprint

        fp = path_fingerprint(path)
        resp["serve_path"] = fp
        with self._path_lock:
            self._path_counts[fp] = self._path_counts.get(fp, 0) + 1
        if self._tracer is not None and request_id:
            self._tracer.event(request_id, "serve_path",
                               fingerprint=fp)
        return fp

    def path_counts_snapshot(self) -> dict:
        """fingerprint -> served-request count (for /metrics)."""
        with self._path_lock:
            return dict(self._path_counts)

    def slo_stats(self):
        """SLO breach counters for /metrics (zeros when no watcher)."""
        if self._slo is None:
            return {"slo_breach_total": 0, "slo_ttft_breach_total": 0,
                    "slo_e2e_breach_total": 0, "slo_dumps_written": 0}
        return self._slo.stats()

    def prefix_cache_stats(self):
        """Prefix-cache counters + pool occupancy for /metrics, or
        None when no pool is attached."""
        return (self._prefix.stats_snapshot()
                if self._prefix is not None else None)

    def tp_stats(self) -> dict:
        """Tensor-parallel serving telemetry for /metrics (ISSUE 10):
        the ``tp_degree`` gauge plus the per-decode-step collective
        byte/count accounting from the compiled HLO (the MULTICHIP
        dryrun technique, parallel/tp.decode_step_collectives).
        Computed ONCE on first success — the accounting compiles a
        1-token decode step AOT, which must never ride the scrape path
        twice, so concurrent scrapes serialize on a lock (the
        continuous engine precomputes at setup; the plain/static
        schedulers pay it on the first scrape). A transient failure is
        NOT cached: the scrape reports zeros and the next one retries.
        tp=1 short-circuits to zeros with no compile."""
        with self._tp_stats_lock:
            if self._tp_stats is not None:
                return self._tp_stats
            from ..parallel.tp import decode_step_collectives

            try:
                self._tp_stats = decode_step_collectives(
                    self.model, self.params)
                return self._tp_stats
            except Exception as e:  # noqa: BLE001 — telemetry must
                # never take the server down; the gauge still reports
                logger.warning("tp collective accounting failed "
                               "(will retry next scrape): %s", e)
                return {"tp_degree": self.tp,
                        "collective_count_per_step": 0,
                        "collective_bytes_per_step": 0,
                        "analytic_floor_bytes": 0,
                        "counts": {}, "bytes": {}}

    def encode_prompt(self, prompt=None, prompt_ids=None) -> list:
        """Text or explicit ids -> validated id list (raises ValueError
        with a caller-presentable message on every bad input)."""
        if prompt_ids is not None:
            try:
                # TypeError (non-iterable payload, nested lists) is as
                # much a client input error as a bad value — normalize
                # to ValueError so serve.py maps it to HTTP 400, not 500.
                # Strings ("123" iterates to [1,2,3]) and non-integral
                # floats (1.9 truncates) would silently generate from
                # ids the client never sent — reject, don't coerce.
                if isinstance(prompt_ids, (str, bytes)):
                    raise ValueError("got a string, not a list")
                ids = []
                for i in prompt_ids:
                    # bool is an int subclass: true/false would coerce
                    # to ids 1/0 — same reject-don't-coerce class
                    if isinstance(i, bool) or int(i) != i:
                        raise ValueError(f"non-integer id {i!r}")
                    ids.append(int(i))
            except (TypeError, ValueError, OverflowError) as e:
                # OverflowError: json.loads accepts Infinity, and
                # int(inf) overflows — still a client input error
                raise ValueError(
                    f"prompt_ids must be a flat list of ints: {e}"
                ) from e
            if self.vocab and any(i >= self.vocab or i < 0 for i in ids):
                raise ValueError(
                    f"prompt id outside [0, {self.vocab}) — nn.Embed "
                    "would silently clamp/wrap it"
                )
        elif prompt is None:
            raise ValueError("pass a prompt or prompt ids")
        elif self.vocab <= 256:
            ids = list(str(prompt).encode("utf-8"))
            if any(i >= self.vocab for i in ids):
                raise ValueError(f"prompt byte >= vocab_size {self.vocab}")
        else:
            if self.tokenizer is None:
                raise ValueError(
                    f"vocab_size {self.vocab} > 256 and no BpeLMLoader "
                    "tokenizer found in the run config: pass prompt ids, "
                    "or train through BpeLMLoader for text round-tripping"
                )
            ids = [int(i) for i in self.tokenizer.encode(str(prompt))]
            if any(i >= self.vocab for i in ids):
                raise ValueError(
                    f"tokenizer id >= model vocab_size {self.vocab} — "
                    "the checkpoint and tokenizer disagree"
                )
        if not ids:
            raise ValueError("empty prompt (need at least one token)")
        return ids

    def encode_stop(self, stop) -> list:
        """Wire-level ``stop`` -> validated stop-token id list.

        Accepts a single id / string or a list of them. Strings encode
        through the same text path as prompts (bytes for byte-vocab
        models, the run's BPE tokenizer otherwise) and must encode to
        EXACTLY one token — the in-graph stop check is per emitted
        token, and silently matching only a suffix of a multi-token
        sequence would stop on the wrong text. Returns [] for None.
        """
        if stop is None:
            return []
        items = stop if isinstance(stop, (list, tuple)) else [stop]
        ids = []
        for s in items:
            if isinstance(s, bool) or isinstance(s, float):
                raise ValueError(f"stop entries are ids or strings, "
                                 f"got {s!r}")
            if isinstance(s, int):
                ids.append(int(s))
            elif isinstance(s, str):
                toks = self.encode_prompt(prompt=s)
                if len(toks) != 1:
                    raise ValueError(
                        f"stop string {s!r} encodes to {len(toks)} "
                        "tokens; only single-token stops are supported "
                        "(pass stop ids for multi-token sequences)"
                    )
                ids.append(int(toks[0]))
            else:
                raise ValueError(f"bad stop entry {s!r}")
        if self.vocab and any(i >= self.vocab or i < 0 for i in ids):
            raise ValueError(f"stop id outside [0, {self.vocab})")
        return ids

    def _check_role(self, max_new: int) -> None:
        """The role gate (disaggregated serving, ISSUE 12): a
        prefill-role replica refuses decode-scale budgets LOUDLY (the
        router mis-routed — serving it would silently re-colocate the
        workload the split exists to separate). Decode and colocated
        roles serve everything: a decode replica must still be able to
        cold-prefill a miss (shipping is an optimization, never a
        correctness dependency)."""
        if self.role == "prefill" and int(max_new) > self.PREFILL_MAX_NEW:
            raise ValueError(
                f"prefill-role replica serves max_new_tokens <= "
                f"{self.PREFILL_MAX_NEW} (got {int(max_new)}): decode "
                "work belongs on a decode-role replica (POST /prefill "
                "ships this prompt's KV pages instead)")

    def prefill_export(self, prompt=None, prompt_ids=None,
                       request_id=None, deadline=None) -> dict:
        """The prefill-role entry (ISSUE 12 tentpole): compute the
        prompt's KV into this replica's pool — paged path when
        supported, scatter-insert fallback otherwise — and export the
        full-block chain as a ship payload for a decode replica.

        NOTHING but pages + token ids ships: the decode replica's warm
        admit recomputes the fed suffix window (which always includes
        the final prompt token) exactly as a cold admit would, so its
        first-token logits — and therefore greedy AND sampled output
        under the request's own seed — are token-identical to a
        colocated run with no sampling state crossing the wire. The
        canonical-rotation contract (PR 5) is what makes the shipped
        bytes position/era-independent: a page is just content + a
        block-table splice on arrival.

        Returns the payload dict (``engine/kvcache.serialize_pages``
        turns it into wire bytes); a prompt too short to fill one block
        returns a payload with ``n_blocks == 0`` — the caller sends
        the decode replica straight to a cold prefill.

        Concurrency (ISSUE 13 satellite): exports COALESCE. Each
        caller enqueues its chain; the first thread to take the
        export-leader lock drains the whole queue under ONE service-
        lock acquisition (computing + exporting every queued chain),
        so a burst of concurrent handoffs pays one lock wait instead
        of N serialized ones — the ``handoff_seconds`` queueing
        component this was measured to dominate. ``prefill_export_
        batches`` / ``prefill_export_max_batch`` make the coalescing
        observable."""
        import time

        from .kvcache import serialize_pages  # noqa: F401 (re-export)

        t0 = time.monotonic()
        if deadline is not None and deadline.expired(t0):
            raise DeadlineExceeded("deadline expired before prefill")
        if self._prefix is None:
            raise ValueError("prefill_export needs a prefix cache "
                             "(serving.prefix_cache.enabled)")
        ids = self.encode_prompt(prompt, prompt_ids)
        pf = self._prefix
        empty = {"version": 1, "block_tokens": pf.block, "n_blocks": 0,
                 "token_ids": [], "tp_geometry": {"tp": pf._tp},
                 "leaves": {}}
        if len(ids) // pf.block == 0:
            return empty          # nothing exportable: sub-block prompt
        import threading

        item = {"ids": ids, "evt": threading.Event(), "result": None,
                "error": None}
        with self._export_mu:
            self._export_q.append(item)
        while not item["evt"].is_set():
            if self._export_leader.acquire(blocking=False):
                try:
                    self._drain_export_queue()
                finally:
                    self._export_leader.release()
            else:
                # a leader is processing; it drains until the queue is
                # empty, so either it takes this item or the loop wins
                # the leader lock on the next spin
                item["evt"].wait(0.002)
        if item["error"] is not None:
            raise item["error"]
        payload = item["result"] or empty
        self.stats["prefill_exports"] = (
            self.stats.get("prefill_exports", 0) + 1)
        if self._tracer is not None and request_id:
            self._tracer.add(request_id, "prefill_export", t0,
                             time.monotonic(),
                             blocks=payload["n_blocks"])
        return payload

    def _drain_export_queue(self) -> None:
        """The export leader's loop: repeatedly drain EVERY queued
        chain and process the batch under one service-lock
        acquisition, until the queue stays empty (a caller enqueueing
        after the final drain becomes the next leader itself). One
        chain's failure is its own — it must not poison batchmates."""
        while True:
            with self._export_mu:
                batch, self._export_q = self._export_q, []
            if not batch:
                return
            with self._lock:
                for it in batch:
                    try:
                        it["result"] = self._export_chain_locked(
                            it["ids"])
                    except Exception as e:  # noqa: BLE001 — per-chain
                        it["error"] = e
            self.stats["prefill_export_batches"] = (
                self.stats.get("prefill_export_batches", 0) + 1)
            self.stats["prefill_export_max_batch"] = max(
                self.stats.get("prefill_export_max_batch", 0),
                len(batch))
            for it in batch:
                it["evt"].set()

    def _export_chain_locked(self, ids):
        """Compute-if-needed + export ONE chain (the leader holds the
        service lock). Paged arm: a 1-token-budget reservation whose
        suffix prefill writes straight into private pages, finished
        immediately so the prompt's blocks adopt zero-copy; scatter
        arm: warm_prefill's plan_insert + capture. Spilled blocks
        promote first — a demoted chain is as exportable as a
        resident one."""
        pf = self._prefix
        if pf.spill is not None:
            pf.promote_spilled(ids)
        if pf.cached_block_count(ids) < len(ids) // pf.block:
            done = False
            if pf.paged:
                res = pf.paged_prefill(self.params, ids, 1)
                if res is not None:
                    _, cache, _, plan = res
                    pf.paged_finish(plan, [], 0)
                    done = True
            if not done and not getattr(pf, "window", 0):
                # no scatter arm for ring layouts: a dry ring pool
                # exports whatever chain is already resident
                pf.warm_prefill(self.params, ids, len(ids) + 1)
        return pf.export_pages(ids)

    def export_cached_pages(self, prompt=None, prompt_ids=None,
                            request_id=None) -> dict:
        """Peer page migration's EXPORT-ONLY entry (ISSUE 13): ship
        whatever full-block chain this replica already holds for the
        prompt — resident pages, plus spilled pages promoted (and
        checksum-verified) on the way out — WITHOUT computing anything
        missing. The fleet manager's miss-driven peer pulls and
        restart re-warm both call this on the holder; a replica that
        holds nothing answers ``n_blocks == 0`` and the puller falls
        back cold. Any role with a pool serves it."""
        if self._prefix is None:
            raise ValueError("export_cached_pages needs a prefix cache "
                             "(serving.prefix_cache.enabled)")
        ids = self.encode_prompt(prompt, prompt_ids)
        pf = self._prefix
        with self._lock:
            if pf.spill is not None:
                pf.promote_spilled(ids)
            payload = pf.export_pages(ids)
        if payload is None:
            payload = {"version": 1, "block_tokens": pf.block,
                       "n_blocks": 0, "token_ids": [],
                       "tp_geometry": {"tp": pf._tp}, "leaves": {}}
        self.stats["peer_exports"] = (
            self.stats.get("peer_exports", 0) + 1)
        return payload

    def import_remote_pages(self, payload, origin: str = "ship") -> dict:
        """The decode-role entry: land a shipped page chain in this
        replica's pool (``bytes`` payloads deserialize here), making
        the prompt's prefix a radix HIT — the very next ``generate``
        for it admits as a zero-recompute block-table pointer update.
        ``origin`` tags the adopted nodes for path provenance (ISSUE
        18): "ship" for the disagg handoff, "pull" when the fleet
        manager dragged the chain here as a peer pull. Runs under the
        service lock (the scheduler's tick-start
        ``refresh_cache_from_pool`` absorbs the import's pool
        donation, same contract as batch-1 speculative requests)."""
        from .kvcache import deserialize_pages

        if self._prefix is None:
            raise ValueError("import_remote_pages needs a prefix cache "
                             "(serving.prefix_cache.enabled)")
        if isinstance(payload, (bytes, bytearray, memoryview)):
            payload = deserialize_pages(bytes(payload))
        with self._lock:
            receipt = self._prefix.import_pages(payload, origin=origin)
        self.stats["remote_admits"] = (
            self.stats.get("remote_admits", 0) + 1)
        return receipt

    def validate_request(self, req: dict) -> None:
        """Cheap host-side validation of a wire-format request body
        (the dict serve.py reads off the socket): raises the same
        ``ValueError`` the matching ``generate()`` call would, WITHOUT
        touching the device. serve.py runs it before committing a 200
        ``text/event-stream`` response, so a bad streaming request
        gets the 400 its non-streaming twin gets instead of a 200 +
        SSE error event (ADVICE r5). Numeric coercions mirror
        serve._run_request — a non-numeric ``max_new_tokens`` is as
        much a 400 as an over-budget one."""
        ids = self.encode_prompt(req.get("prompt"),
                                 req.get("prompt_ids"))
        stops = self.encode_stop(req.get("stop"))
        max_new = int(req.get("max_new_tokens", 64))
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self._check_role(max_new)
        float(req.get("temperature", 0.0))
        int(req.get("top_k", 0))
        float(req.get("top_p", 0.0))
        int(req.get("seed", 0))
        speculative = int(req.get("speculative", 0))
        self._validate_budget(ids, max_new, stops,
                              speculative=speculative)

    def _validate_budget(self, ids, max_new: int, stops,
                         speculative: int = 0) -> None:
        """Scheduler-specific budget/shape checks (subclasses refine):
        the plain and static paths reject prompt + budget past
        ``max_len`` at enqueue."""
        max_len = int(getattr(self.model, "max_len", 0) or 0)
        if max_len and len(ids) + max_new > max_len:
            raise ValueError(
                f"prompt ({len(ids)} tokens) + max_new_tokens "
                f"({max_new}) exceeds model.max_len {max_len}")

    def decode_text(self, ids):
        """Generated ids -> text, when the model has a text form
        (byte vocab or a recovered tokenizer); else None."""
        import numpy as np

        ids = np.asarray(ids).reshape(-1)
        if self.vocab and self.vocab <= 256:
            return bytes(int(t) for t in ids).decode(
                "utf-8", errors="replace"
            )
        if self.tokenizer is not None:
            # replace (not raise) on ids past the learned vocab: BPE
            # training can stop short of the configured head size, and
            # an undertrained model may emit those ids
            return self.tokenizer.decode(ids, errors="replace")
        return None

    def generate(self, prompt=None, prompt_ids=None,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 speculative: int = 0, stop=None,
                 request_id=None, deadline=None) -> dict:
        """One validated generation request ->
        ``{"ids", "text"?, "stop_reason", "speculative"?}``.

        ``stop``: stop-token ids and/or single-token strings; the
        in-graph loop exits as soon as every row is done, so a stopped
        request costs chip time proportional to what it EMITS, not its
        budget. The stop token is excluded from the response (its
        presence is reported as ``stop_reason: "stop"``).

        ``request_id``: the request-scoped trace id (ISSUE 8) — keys
        this request's spans/SLO observation when a tracer is attached;
        otherwise inert.

        ``deadline``: optional :class:`reqtrace.Deadline` (ISSUE 9).
        The plain path honors it at dispatch boundaries only (checked
        at entry and after the lock wait — a generation already on the
        chip runs out); the continuous scheduler overrides this with
        true mid-flight cancellation at chunk absorbs.
        """
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np

        from .generate import generate

        t_req = time.monotonic()
        if deadline is not None and deadline.expired(t_req):
            raise DeadlineExceeded(
                "deadline expired before dispatch")
        self._check_role(max_new_tokens)
        ids = self.encode_prompt(prompt, prompt_ids)
        stops = self.encode_stop(stop)
        path = self._base_path(speculative)
        arr = jnp.asarray(np.asarray(ids, np.int32)[None, :])
        with self._lock:
            if deadline is not None and deadline.expired():
                # the lock wait ate the budget: shed before spending
                # chip time on tokens nobody is waiting for
                raise DeadlineExceeded(
                    "deadline expired waiting for the chip")
            emitted = None
            if speculative > 0:
                new_ids, stats = self._adaptive_speculative(
                    arr, int(max_new_tokens), int(speculative),
                    float(temperature), int(top_k), float(top_p),
                    int(seed), stops,
                )
                resp = self._response(new_ids, stops=stops,
                                      emitted=len(new_ids))
                resp["speculative"] = stats
                if self._tracer is not None and request_id:
                    self._tracer.event(
                        request_id, "spec",
                        tokens_per_call=stats.get("tokens_per_call"),
                        model_calls=stats.get("model_calls"),
                        disabled=stats.get("speculation_disabled"))
                if (self._prefix is not None
                        and stats.get("prefix_hit_tokens")):
                    # the pool-shared spec arm warm-prefilled through
                    # the prefix cache — a warm admit, with the pool
                    # events warm_prefill stashed
                    path["mode"] = "warm"
                    path.update(getattr(self._prefix,
                                        "last_warm_flags", {}))
                self._finalize_path(resp, path, request_id)
                self._observe_request(request_id, t_req, resp)
                return resp
            # row_rngs (not rng): the row stream is key(seed)
            # EXACTLY, matching what the micro-batched service
            # passes per row — same request + seed samples the
            # same tokens whether or not it shared a batch
            row_rngs = jnp.stack([jax.random.key(int(seed))])
            if (self._prefix is not None and not stops
                    and int(max_new_tokens) >= 1
                    and len(ids) + int(max_new_tokens)
                    <= int(self.model.max_len)):
                # paged prefix cache (engine/kvcache.py): prefill only
                # the uncached suffix, then the normal step loop. Same
                # per-(step, row) key layout as generate(), so sampled
                # output matches the cold path; the stop-token path
                # stays cold (its fused single-dispatch loop builds its
                # own cache in-graph). Out-of-budget requests also fall
                # through, so generate() raises the usual ValueError.
                # None = the pool cannot serve this request at all
                # (e.g. a ring layout's dry pool — no scatter arm
                # exists for window models): the cold path below
                # serves it, counted as a pool fallback.
                new_ids = self._generate_prefix_cached(
                    ids, int(max_new_tokens), float(temperature),
                    int(top_k), float(top_p), row_rngs)
                if new_ids is not None:
                    resp = self._response(new_ids, stops=stops)
                    path.update(getattr(self, "_last_path_info", {}))
                    self._finalize_path(resp, path, request_id)
                    self._observe_request(request_id, t_req, resp)
                    return resp
            if stops:
                out, lengths = generate(
                    self.model, self.params, arr,
                    max_new_tokens=int(max_new_tokens),
                    temperature=float(temperature),
                    top_k=int(top_k), top_p=float(top_p),
                    row_rngs=row_rngs, stop_tokens=stops,
                    return_lengths=True,
                )
                emitted = int(lengths[0])
            else:
                out = generate(
                    self.model, self.params, arr,
                    max_new_tokens=int(max_new_tokens),
                    temperature=float(temperature),
                    top_k=int(top_k), top_p=float(top_p),
                    row_rngs=row_rngs,
                )
        resp = self._response(np.asarray(out[0, arr.shape[1]:]),
                              stops=stops, emitted=emitted)
        self._finalize_path(resp, path, request_id)
        self._observe_request(request_id, t_req, resp)
        return resp

    def _generate_prefix_cached(self, ids, max_new: int,
                                temperature: float, top_k: int,
                                top_p: float, row_rngs):
        """Batch-1 decode through the paged prefix pool. TWO arms:

        - **paged** (kv_cache_spec paged=True, pool healthy): the
          cached prefix is a block-table pointer entry — ZERO admit
          copy — the suffix prefills straight into private pool pages,
          decode reads the pool in place (ops/flash paged kernel on
          TPU), and the finished request's pages adopt into the radix
          index with no capture kernel.
        - **scatter fallback** (unsupported layouts / dry pool):
          kvcache.warm_prefill — cached blocks scatter into a
          contiguous cache, suffix-only prefill, capture-copy insert.

        Both use the SAME step-loop + per-(step, row) key folding as
        engine/generate's eager path, so output matches the cold path
        token for token (float-tolerance exact, like every other
        batched-vs-solo contract in this stack). Caller holds the lock
        and has validated budget/stops."""
        import jax.numpy as jnp
        import numpy as np

        from .generate import _decode_fns, _fold_all_rows, _sample_rows
        from .kvcache import _paged_decode_fns, page_origin_flags

        # path provenance stash (ISSUE 18): which arm served this
        # request + the pool events it consumed; the caller merges it
        # into the request's serve-path fingerprint. Safe as an
        # instance attr — the caller holds the service lock.
        self._last_path_info = {"mode": "cold"}
        if temperature <= 0:
            keys_at = lambda i: row_rngs                   # noqa: E731
        else:
            all_keys = _fold_all_rows(row_rngs, max_new)
            keys_at = lambda i: all_keys[i]                # noqa: E731
        if self._prefix.paged:
            res = self._prefix.paged_prefill(self.params, ids, max_new)
            if res is not None:
                last_logits, cache, tables, plan = res
                step = _paged_decode_fns(
                    self.model, self._prefix.nb_max, temperature,
                    top_k, top_p)
                token = _sample_rows(keys_at(0), last_logits,
                                     temperature, top_k, top_p)
                out = [token[:, None]]
                L = len(ids)
                try:
                    for i in range(1, max_new):
                        token, cache = step(
                            self.params, cache, token, keys_at(i),
                            tables,
                            jnp.asarray([L + i - 1], jnp.int32))
                        out.append(token[:, None])
                    row = np.asarray(jnp.concatenate(out, axis=1))[0]
                except Exception:
                    # a failed step must not strand refs or leak
                    # pages. `cache` may be the pytree just DONATED
                    # into the failing dispatch — syncing dead leaves
                    # would wedge the shared pool for every later
                    # request, so reset instead (the plan's refs and
                    # pages die with the index; finishing against a
                    # fresh index would double-free).
                    if self._prefix.pool_alive(cache):
                        self._prefix.sync_pool_from_cache(cache)
                        self._prefix.paged_finish(plan, [], 0)
                    else:
                        self._prefix.reset_pool()
                    raise
                self._prefix.sync_pool_from_cache(cache)
                # zero-copy insert: prompt AND decoded tokens become
                # sharable in place
                self._prefix.paged_finish(
                    plan, [int(t) for t in row], max_new)
                self._prefix.count_batch1(paged=True)
                self._last_path_info = {
                    "mode": "paged",
                    "wrap": bool(plan.get("ring_wrap")),
                    **page_origin_flags(plan.get("nodes"))}
                return row
        self._prefix.count_batch1(paged=False)
        # pool-fallback accounting (ISSUE 15): a healthy-but-dry paged
        # pool degrades as "dry_pool"; a structurally unpaged pool
        # counts its own reason (gpt2_layout / undersized)
        self._prefix.count_fallback(
            "dry_pool" if self._prefix.paged else "")
        if getattr(self._prefix, "window", 0):
            # ring layouts have NO scatter arm (a rolling contiguous
            # cache is position-dependent): the caller's cold path
            # serves this request instead
            return None
        # a dry-pool fall-through from the paged arm already recorded
        # this request's lookup inside paged_plan — recording again
        # here would double-count prefix_hit_tokens for the SAME
        # request (the counter feeds /metrics and the bench gates)
        last_logits, cache, hit = self._prefix.warm_prefill(
            self.params, ids, len(ids) + max_new,
            record=not self._prefix.paged)
        if hit:
            self._last_path_info = {
                "mode": "warm",
                **getattr(self._prefix, "last_warm_flags", {})}
        _, step = _decode_fns(self.model, temperature, top_k, top_p)
        token = _sample_rows(keys_at(0), last_logits, temperature,
                             top_k, top_p)
        out = [token[:, None]]
        for i in range(1, max_new):
            token, cache = step(self.params, cache, token, keys_at(i))
            out.append(token[:, None])
        return np.asarray(jnp.concatenate(out, axis=1))[0]

    # Speculative fail-safe (VERDICT r4 weak #3 / next #5): prompt-
    # lookup acceptance is workload-dependent — repetitive text accepts
    # ~3 tokens/call, adversarial (sampled natural) text ~1.0 — so the
    # server probes the first chunk and finishes the request with
    # plain decode when projected speedup = acceptance / cost_ratio
    # falls under 1. The cost ratio (verify call / vanilla step) is
    # platform-dependent: isolated-dispatch measurements said ~1.5
    # (BASELINE.md r4), but the r5 end-to-end adversarial bench arm
    # measures ~1.0-1.1 on this chip — batch-1 decode is HBM-bound,
    # and a (D+1)-token verify streams the same weight bytes as a
    # 1-token step — so speculation only mildly loses even at zero
    # acceptance there. 1.25 is the conservative middle; deployments
    # can override the attribute with their own measured ratio.
    SPEC_PROBE = 32
    SPEC_MIN_TOKENS_PER_CALL = 1.25

    def _spec_pad_to(self, t0: int, budget: int, draft: int):
        """Length-bucket a speculative prompt on pad-capable models:
        arbitrary prompt lengths would otherwise pay a fresh XLA
        compile each (seconds per new length)."""
        if not self._pad_ok:
            return None
        bucket = 16
        while bucket < t0:
            bucket *= 2
        limit = (int(self.model.max_len) - budget - 2 * (draft + 1))
        pad_to = min(bucket, limit)
        return pad_to if pad_to > t0 else None

    def _spec_generate(self, arr, budget: int, draft: int,
                       temperature: float, top_k: int, top_p: float,
                       rng, stops):
        """One speculative phase, POOL-SHARED when possible (ISSUE 7):
        with a prefix pool attached, the prompt warm-prefills through
        it (cached blocks + suffix-only prefill) and the spec loop
        continues from that cache — the early-exit draft
        (``speculative_draft_layers``) shares the same cache, so BOTH
        target and draft skip the shared prefix's prefill. Without a
        pool (or when the budget + overshoot slack does not fit
        ``max_len``), the plain length-bucketed
        ``generate_speculative`` runs as before."""
        import numpy as np

        from .generate import generate_speculative

        t0 = arr.shape[1]
        # getattr: tests drive _adaptive_speculative on a bare
        # __new__-built service with no _setup (no pool, no draft cfg)
        dl = getattr(self, "_spec_draft_layers", 0)
        prefix = getattr(self, "_prefix", None)
        L = t0 + int(budget) + 2 * (int(draft) + 1)
        if (prefix is not None and L <= int(self.model.max_len)
                and not getattr(prefix, "window", 0)):
            ids = [int(t) for t in np.asarray(arr)[0]]
            # route through the pool only on an actual prefix HIT:
            # the warm path's executables key on the EXACT (t0, L) —
            # worth one compile when the prefill skip pays for it,
            # but cold spec traffic of arbitrary lengths stays on the
            # length-BUCKETED generate_speculative below (the probe
            # must not count: it is not a served lookup)
            probe, _, c = prefix.lookup(ids, record=False)
            prefix.release(probe)
            if c:
                return self._spec_from_pool(
                    prefix, ids, L, budget, draft, temperature,
                    top_k, top_p, rng, stops, dl)
        return generate_speculative(
            self.model, self.params, arr, max_new_tokens=budget,
            draft_len=draft, return_stats=True,
            temperature=temperature, top_k=top_k, top_p=top_p,
            rng=rng, pad_to=self._spec_pad_to(t0, budget, draft),
            stop_tokens=stops or None, draft_layers=dl)

    def _spec_from_pool(self, prefix, ids, L, budget, draft,
                        temperature, top_k, top_p, rng, stops, dl):
        """The pool-shared speculative arm (ISSUE 7): warm prefill
        (cached blocks + suffix-only feed) continuing into the fused
        spec loop; target AND early-exit draft skip the shared
        prefix's prefill."""
        from .generate import speculative_from_cache

        last_logits, cache, hit = prefix.warm_prefill(
            self.params, ids, L)
        out, stats = speculative_from_cache(
            self.model, self.params, ids, cache, last_logits, L,
            budget, draft_len=draft, temperature=temperature,
            top_k=top_k, top_p=top_p, rng=rng,
            stop_tokens=stops or None, draft_layers=dl)
        stats["prefix_hit_tokens"] = hit
        return out, stats

    def _adaptive_speculative(self, arr, max_new: int, draft: int,
                              temperature: float, top_k: int,
                              top_p: float, seed: int, stops):
        """Speculative decode with the acceptance probe: run the first
        ``SPEC_PROBE`` tokens speculatively, then either keep
        speculating (acceptance >= the bar) or finish with plain
        decode (``speculation_disabled: true`` in the stats). Greedy
        output is bit-identical either way (greedy speculation ==
        greedy decode, phase-split or not); sampled output stays
        distribution-exact (each phase's rejection sampler is exact
        given its prefix — the rng PATH differs from the single-shot
        call, the law does not).

        Returns ``(ids, stats)`` — ids are the emitted tokens (stop
        token included when one fired; the response layer strips it).
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        from .generate import generate

        t0 = arr.shape[1]
        probe = min(self.SPEC_PROBE, max_new)
        key = jax.random.key(seed)
        out, stats = self._spec_generate(
            arr, probe, draft, temperature, top_k, top_p, key, stops)
        emitted = stats["tokens_emitted"]
        ids = [int(t) for t in np.asarray(out)[0, t0:t0 + emitted]]
        stats = dict(stats,
                     probe_tokens_per_call=stats["tokens_per_call"],
                     speculation_disabled=False)
        rest = max_new - probe
        if stops and ids and ids[-1] in stops:
            # a stop landing exactly on the probe's last slot reports
            # stopped=False from generate_speculative (emitted ==
            # budget) — continuing past it would hand the client
            # post-stop tokens
            stats["stopped"] = True
        if stats["stopped"] or rest <= 0:
            return ids, stats
        arr2 = jnp.concatenate(
            [arr, jnp.asarray(np.asarray(ids, np.int32))[None, :]],
            axis=1,
        )
        t1 = arr2.shape[1]
        key2 = jax.random.fold_in(key, 1)
        if stats["probe_tokens_per_call"] >= self.SPEC_MIN_TOKENS_PER_CALL:
            out2, s2 = self._spec_generate(
                arr2, rest, draft, temperature, top_k, top_p, key2,
                stops)
            em2 = s2["tokens_emitted"]
            calls = stats["model_calls"] + s2["model_calls"]
            stopped = s2["stopped"]
        else:
            # acceptance under the bar: plain decode for the rest —
            # each remaining token is one model call, which is exactly
            # what a losing speculative loop must fall back to
            row_rngs = jnp.stack([key2])
            if stops:
                out2, lengths = generate(
                    self.model, self.params, arr2, rest,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    row_rngs=row_rngs, stop_tokens=stops,
                    return_lengths=True,
                )
                em2 = int(lengths[0])
            else:
                out2 = generate(
                    self.model, self.params, arr2, rest,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    row_rngs=row_rngs,
                )
                em2 = rest
            calls = stats["model_calls"] + em2
            stopped = bool(stops) and em2 < rest
            stats["speculation_disabled"] = True
        ids += [int(t) for t in np.asarray(out2)[0, t1:t1 + em2]]
        if stops and ids and ids[-1] in stops:
            stopped = True
        stats.update(
            model_calls=calls,
            tokens_emitted=emitted + em2,
            stopped=stopped,
            tokens_per_call=round((emitted + em2) / max(calls, 1), 3),
        )
        return ids, stats

    def _response(self, new_ids, stops=(), emitted=None) -> dict:
        """Generated row -> wire response (ONE place: the batched and
        serialized paths must never drift apart).

        ``emitted`` = tokens the model actually produced for this row
        (stop token included, frozen pad tail excluded); the stop
        token itself is stripped from the wire ids/text and reported
        as ``stop_reason: "stop"``.
        """
        ids = [int(t) for t in new_ids]
        reason = "length"
        if emitted is not None:
            ids = ids[:emitted]
        if stops and ids and ids[-1] in stops:
            ids = ids[:-1]
            reason = "stop"
        resp: dict = {"ids": ids, "stop_reason": reason}
        text = self.decode_text(ids)
        if text is not None:
            resp["text"] = text
        # every scheduler's responses funnel through here — the ONE
        # place a tokens-served counter stays scheduler-agnostic
        # (surfaced by serve.py's /metrics)
        stats = getattr(self, "stats", None)
        if stats is not None:
            stats["tokens_generated"] = (
                stats.get("tokens_generated", 0) + len(ids))
            if (getattr(self, "pool_refusal_reason", "")
                    and getattr(self, "_prefix", None) is None):
                # pool-fallback observability (ISSUE 15): a REFUSED
                # pool means every served request ran without it —
                # counted here so even the plain scheduler's /metrics
                # carries the degradation
                stats["pool_refused_requests"] = (
                    stats.get("pool_refused_requests", 0) + 1)
        return resp


class BatchedGenerationService(GenerationService):
    """``GenerationService`` with a micro-batch scheduler.

    The plain service serializes requests with a lock: one request
    occupies the chip while others queue, even though ``generate()``
    is batch-capable and decode throughput scales with batch (the
    ``decode`` bench rung runs batch 8 at ~10x batch-1 aggregate
    tok/s). Here concurrent requests queue into a single worker that
    groups COMPATIBLE requests — same (prompt length, max_new_tokens,
    temperature, top_k, top_p) — within a short batching window into
    one batched prefill + shared decode loop. Each request keeps its
    own sampling stream (``generate(row_rngs=...)``), so a request's
    output never depends on which requests shared its batch.

    For RoPE families (the Llama/Mistral family: shift-invariant
    positions + per-row pad masking, ``models/llama.py pad_lens``),
    requests of DIFFERENT prompt lengths batch together within a
    128-token length bucket: shorter rows are LEFT-padded and their
    pad slots masked, which is token-exact vs solo execution
    (tests/test_generate.py). Absolute-position families (GPT-2) and
    rolling-window models group by exact prompt length instead (one
    batch-wide position counter; ring eviction differs per row).
    Speculative requests stay batch-1 by construction and bypass the
    scheduler. ``stats`` (surfaced via /healthz) records how much
    sharing actually happened.
    """

    PAD_BUCKET = 128

    def _setup(self, model, params, tokenizer=None,
               max_batch: int = 8, window_ms: float = 25.0,
               spec_draft_layers: int = 0, tracer=None, slo=None):
        import queue
        import threading

        super()._setup(model, params, tokenizer,   # sets _pad_ok
                       spec_draft_layers=spec_draft_layers,
                       tracer=tracer, slo=slo)
        self._max_batch = int(max_batch)
        self._window_s = float(window_ms) / 1e3
        self._queue: "queue.Queue" = queue.Queue()
        self.stats = {"requests": 0, "batches": 0,
                      "batched_requests": 0, "max_batch_size": 0}
        self._worker_thread = threading.Thread(
            target=self._worker, daemon=True, name="gen-batcher"
        )
        self._worker_thread.start()

    def generate(self, prompt=None, prompt_ids=None,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 speculative: int = 0, stop=None,
                 request_id=None, deadline=None) -> dict:
        import threading
        import time

        if speculative > 0:
            # batch-1 by construction (single cache position counter);
            # runs under the parent's lock like any other chip user
            return super().generate(
                prompt=prompt, prompt_ids=prompt_ids,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                speculative=speculative, stop=stop,
                request_id=request_id, deadline=deadline,
            )
        t_req = time.monotonic()
        if deadline is not None and deadline.expired(t_req):
            raise DeadlineExceeded("deadline expired before dispatch")
        # validate in the CALLER's thread: bad input must raise here
        # (HTTP 400), not poison the worker. The budget rule lives in
        # _validate_budget (ONE owner, shared with serve.py's pre-SSE
        # validate_request): group keys pin max_new_tokens, so if
        # every member individually fits, padding to the longest
        # member's length fits too — one oversized request can never
        # fail its batchmates
        ids = self.encode_prompt(prompt, prompt_ids)
        stops = self.encode_stop(stop)
        self._validate_budget(ids, int(max_new_tokens), stops)
        req = {
            "ids": ids,
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k), "top_p": float(top_p),
            "seed": int(seed),
            # per-ROW stop sets in the loop executable, so requests
            # with different stops still share a batch (not in the key)
            "stop": stops,
            "deadline": deadline,
            "event": threading.Event(),
        }
        # group key computed HERE, in the caller's thread: a raising
        # key function inside the worker can strand a request that is
        # in neither batch nor stash — its event would never be set and
        # this wait() would block forever (advisor r4)
        req["key"] = self._group_key(req)
        self._queue.put(req)
        req["event"].wait()
        if "error" in req:
            raise req["error"]
        self._observe_request(request_id, t_req, req["result"])
        return req["result"]

    def _group_key(self, req):
        n = len(req["ids"])
        length_key = (
            -(-n // self.PAD_BUCKET) if self._pad_ok else n
        )
        return (length_key, req["max_new_tokens"],
                req["temperature"], req["top_k"], req["top_p"])

    def _worker(self):
        import logging
        import queue
        import time

        stash: list = []
        while True:
            # the OUTER try guards everything, including the grouping
            # logic: an exception that escaped it would kill this
            # thread silently and hang every future request behind a
            # queue nobody drains
            batch = []
            try:
                if stash:
                    first = stash.pop(0)
                else:
                    first = self._queue.get()
                # requests carry their precomputed "key" (set in the
                # caller's thread at enqueue): the worker never runs
                # key logic, so no exception here can strand a request
                # outside both batch and stash with its event unset
                batch.append(first)
                key = first["key"]
                # drain compatible stashed requests first
                rest = []
                for r in stash:
                    (batch if r["key"] == key
                     and len(batch) < self._max_batch else rest).append(r)
                stash = rest
                deadline = time.monotonic() + self._window_s
                while len(batch) < self._max_batch:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=left)
                    except queue.Empty:
                        break
                    if nxt["key"] == key:
                        batch.append(nxt)
                    else:
                        stash.append(nxt)
                self._run_batch(batch)
            except Exception as e:  # noqa: BLE001 — surfaced per request
                logging.getLogger(__name__).exception(
                    "batch worker error (batch of %d)", len(batch)
                )
                for r in batch:
                    r["error"] = e
                    r["event"].set()

    def _run_batch(self, batch):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from .generate import generate

        # shed members whose deadline expired in the batching window
        # BEFORE forming the batch (ISSUE 9): a static group decodes to
        # the longest member, so one already-dead request would cost
        # everyone its budget
        live = []
        for r in batch:
            dl = r.get("deadline")
            if dl is not None and dl.expired():
                r["error"] = DeadlineExceeded(
                    "deadline expired in the batch queue")
                r["event"].set()
            else:
                live.append(r)
        if not live:
            return
        batch = live
        t0 = max(len(r["ids"]) for r in batch)
        if self._pad_ok:
            # round the padded length up to a small shape menu (powers
            # of two within the bucket): one XLA compile per (shape,
            # budget, sampling) instead of one per distinct batch-max
            # length, at <=2x extra pad slots. Never past what the
            # model's max_len leaves room for (every member fits by
            # the enqueue check, so t0 itself always does).
            shape = 16
            while shape < t0:
                shape *= 2
            max_len = int(getattr(self.model, "max_len", 0) or 0)
            if max_len:
                shape = min(shape,
                            max_len - batch[0]["max_new_tokens"])
            t0 = max(t0, shape)
        # left-pad; pad slots are masked per row
        # (generate(pad_lens=...)) for pad-capable models, and batches
        # are exact-length by group key otherwise (pad_lens all zero)
        arr = jnp.asarray(np.stack([
            [0] * (t0 - len(r["ids"])) + list(r["ids"]) for r in batch
        ]).astype(np.int32))
        pad_lens = np.asarray(
            [t0 - len(r["ids"]) for r in batch], np.int32
        )
        row_rngs = jnp.stack(
            [jax.random.key(r["seed"]) for r in batch]
        )
        any_stop = any(r["stop"] for r in batch)
        lengths = None
        with self._lock:
            if any_stop:
                # the stop-capable while_loop path: per-row stop sets,
                # so rows with different (or no) stops share the batch;
                # the loop exits once every row is done
                out, lengths = generate(
                    self.model, self.params, arr,
                    max_new_tokens=batch[0]["max_new_tokens"],
                    temperature=batch[0]["temperature"],
                    top_k=batch[0]["top_k"], top_p=batch[0]["top_p"],
                    row_rngs=row_rngs,
                    pad_lens=(jnp.asarray(pad_lens)
                              if pad_lens.any() else None),
                    stop_tokens=[r["stop"] for r in batch],
                    return_lengths=True,
                )
                lengths = np.asarray(lengths)
            else:
                out = generate(
                    self.model, self.params, arr,
                    max_new_tokens=batch[0]["max_new_tokens"],
                    temperature=batch[0]["temperature"],
                    top_k=batch[0]["top_k"], top_p=batch[0]["top_p"],
                    row_rngs=row_rngs,
                    pad_lens=(jnp.asarray(pad_lens)
                              if pad_lens.any() else None),
                )
        new = np.asarray(out[:, t0:])
        self.stats["requests"] += len(batch)
        self.stats["batches"] += 1
        if len(batch) > 1:
            self.stats["batched_requests"] += len(batch)
        self.stats["max_batch_size"] = max(
            self.stats["max_batch_size"], len(batch)
        )
        for i, r in enumerate(batch):
            r["result"] = self._response(
                new[i], stops=r["stop"],
                emitted=None if lengths is None else int(lengths[i]),
            )
            # micro-batched requests always run the cold full-prefill
            # path (no pool on this scheduler) — fingerprint is the
            # base layout/geometry
            self._finalize_path(r["result"], self._base_path())
            r["event"].set()


def load_generation_stack(config, use_ema: bool = False,
                          tensor_parallel: int = 0):
    """``(model, params, tokenizer | None)`` for ``config.resume``.

    ``tensor_parallel`` (ISSUE 10; CLI ``--tp`` wins over the config's
    ``serving.tensor_parallel``, both default 1 = single-chip): shard
    the serving model over a ``{"tensor": tp}`` mesh — weights per the
    model's own megatron ``partition_rules()``, KV caches and the
    paged pool on the head axis — so prefill/admit/decode run as ONE
    SPMD program with all-reduce collectives instead of a single-chip
    dispatch. Geometry that cannot shard (kv heads, d_ff, vocab not
    divisible by tp) refuses loudly HERE, before any executable
    builds."""
    from ..parallel.tp import (
        serving_mesh, shard_serving_params, validate_tp_geometry,
    )

    assert config.resume is not None, "generation requires a checkpoint (-r)"
    dist.initialize()  # multi-host rendezvous parity with train.py/test.py
    tp = int(tensor_parallel or 0) or int(
        (config.get("serving") or {}).get("tensor_parallel") or 1)
    kvq = str((config.get("serving") or {}).get("kv_quant") or "")
    if kvq:
        # int8-KV decode cache (ISSUE 15): a SERVING mode — the scale
        # leaves are cache variables, not params — so the serving
        # section can switch it on over a full-precision training
        # arch without touching the checkpoint
        config["arch"].setdefault("args", {})["kv_quant"] = kvq
    mesh = serving_mesh(tp) if tp > 1 else mesh_from_config(config)
    model = inject_mesh(config.init_obj("arch", MODELS), mesh)
    if not hasattr(model, "max_len"):
        raise SystemExit(
            f"arch {type(model).__name__} has no decode support"
        )
    if tp > 1:
        validate_tp_geometry(model, tp)
        logger.info("tensor-parallel serving: tp=%d over %s", tp,
                    [str(d) for d in mesh.devices.flat])

    serving_meta = load_serving_meta(config.resume)
    if serving_meta is not None:
        # Params-only serving artifact: the artifact's config.json
        # already carries the serving arch args, so the model above IS
        # the serving model — restore its param tree directly; there is
        # no TrainState (and --ema is moot: the weight choice was baked
        # in at artifact-production time).
        if use_ema:
            logger.warning(
                "--ema ignored: %s is a params-only serving artifact "
                "(quantized/merged from %s)", config.resume,
                serving_meta.get("source_params", "params"),
            )
        template = jax.eval_shape(
            lambda: model.init(jax.random.key(0), model.batch_template(1))
        )["params"]
        # Restore sharded over the mesh per the model's partition rules
        # (the quant tree's kernel_q leaves match the same `/kernel`
        # rule patterns; scale vectors replicate). A host-local restore
        # + device_put would break on multi-host meshes.
        rules = (model.partition_rules()
                 if hasattr(model, "partition_rules") else [])
        # mesh passed through: the artifact's recorded tp_geometry is
        # validated against it BEFORE orbax touches a byte — a layout
        # the artifact cannot shard refuses loudly instead of failing
        # deep inside a jit (ISSUE 10 satellite)
        params = restore_serving_params(
            config.resume, template, apply_rules(template, mesh, rules),
            mesh=mesh,
        )
    else:
        state, _ = restore_template_state(config, model, mesh)
        params = (
            state.ema_params
            if use_ema and state.ema_params is not None else state.params
        )
    if tp > 1:
        # idempotent when the restore already materialized sharded
        # leaves; covers template paths that fell through replicated
        params = shard_serving_params(model, params, mesh)
    return model, params, tokenizer_from_config(config)
