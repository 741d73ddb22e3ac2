"""DP×TP serving: N independent tensor-parallel groups in ONE process.

PR 10 made a replica span multiple chips (``--tp N``: one wide SPMD
program). This module is the explicit follow-on (ISSUE 12 / ROADMAP
item 3's geometry half): one *process* now runs ``dp`` independent
engines, each on its own ``tp``-chip group tiling the local device
list — so a decode-role replica can run several small TP groups
(decode is bandwidth-bound; small groups keep the batch per group in
the sweet spot) while a prefill-role replica runs one wide group
(prefill is compute-bound; width buys FLOPs).

Groups are fully independent: each has its own model instance (its own
group-local ``{"tensor": tp}`` mesh from ``parallel/tp.dp_group_mesh``),
its own sharded param copy, its own paged prefix pool, and its own
scheduler thread. NOTHING crosses groups in-graph — the only
cross-group machinery is host-side placement:

- a request routes to the group whose pool holds the deepest cached
  prefix (the in-process twin of the fleet router's cache-aware
  placement), bounded by a load spread so a hot prefix never queues
  behind itself while sibling groups idle; no match = least-loaded,
  ties rotate;
- a page import (``import_remote_pages``) lands on one group's pool,
  and the radix probe above is what steers the follow-up ``generate``
  to that same group — the import IS the affinity record.

Token-exactness is inherited, not re-proven: a request's tokens depend
only on its own prompt, seed, and sampling config (the continuous
engine's contract), and every group runs identical weights — so which
group serves a request cannot change its output, and (dp=2, tp=2) is
token-identical to (dp=1, tp=1) by construction (asserted anyway in
tests/test_disagg.py).

At ``tp == 1`` a group has no mesh: its params are COMMITTED to the
group's device, and jax places every dispatch there (uncommitted
engine state follows committed inputs, then lives on-device as donated
jit outputs) — so dp×1 really is N chips doing independent work, not
N schedulers sharing chip 0.
"""
from __future__ import annotations

import logging
import threading

from ..utils.promtext import percentile

logger = logging.getLogger(__name__)


class _MergedHist:
    """Snapshot-time bucket-sum over the groups' fixed-bucket latency
    histograms — the same aggregation discipline as the fleet poller
    (bucket counters sum exactly; percentile gauges do not)."""

    def __init__(self, hists):
        self._hists = hists

    def snapshot(self) -> dict:
        from ..utils.promtext import add_histograms, zero_histogram

        out = zero_histogram()
        for h in self._hists:
            add_histograms(out, h.snapshot())
        return out


class _StatsView(dict):
    """The facade's ``stats`` dict: a fresh merge of the group
    engines' counters plus the facade's own. Writes (serve.py bumps
    ``deadline_expired`` on pre-dispatch 504s) forward their DELTA to
    the facade's persistent own-counter store, so a counter bumped
    through one snapshot survives into the next."""

    def __init__(self, data, own):
        super().__init__(data)
        self._own = own

    def __setitem__(self, key, value):
        base = self.get(key, 0)
        if isinstance(value, (int, float)) and isinstance(
                base, (int, float)):
            self._own[key] = self._own.get(key, 0) + (value - base)
        else:
            self._own[key] = value
        super().__setitem__(key, value)


class DataParallelService:
    """N independent group engines behind ONE service facade exposing
    the same surface serve.py speaks (generate / validate_request /
    stats / metrics accessors), so the HTTP layer cannot tell dp=4
    from dp=1."""

    def __init__(self, engines, load_spread: float = 4.0):
        if not engines:
            raise ValueError("DataParallelService needs >= 1 engine")
        self._engines = list(engines)
        self._spread = float(load_spread)
        self._rr = 0
        self._lock = threading.Lock()
        self._own_stats: dict = {}
        e0 = self._engines[0]
        self.model = e0.model
        self.arch = e0.arch
        self.vocab = e0.vocab
        self.tokenizer = e0.tokenizer
        self.role = e0.role
        self.tp = e0.tp
        self.dp = len(self._engines)
        self.STREAM_DELTAS = bool(getattr(e0, "STREAM_DELTAS", False))
        self._slots = sum(int(getattr(e, "_slots", 0) or 1)
                          for e in self._engines)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_model_factory(cls, factory, params, dp: int, tp: int,
                           service_cls, tokenizer=None,
                           load_spread: float = 4.0,
                           service_kw=None, service_kw_fn=None):
        """Build ``dp`` group engines: ``factory(mesh)`` returns a
        fresh model instance bound to the group's mesh (None at
        tp=1); ``params`` (host or any-device tree) is re-placed per
        group — sharded over the group mesh at tp>1, committed to the
        group's single device at tp=1. ``service_kw_fn(g)`` overrides
        per-group kwargs (e.g. a recorder only group 0 should own)."""
        import jax

        from ..parallel.tp import (
            dp_group_devices, dp_group_mesh, shard_serving_params,
            validate_dp_geometry, validate_tp_geometry,
        )

        dp, tp = int(dp), int(tp)
        validate_dp_geometry(dp, tp)
        engines = []
        for g in range(dp):
            mesh = dp_group_mesh(g, tp)
            model_g = factory(mesh)
            if mesh is not None:
                validate_tp_geometry(model_g, tp)
                params_g = shard_serving_params(model_g, params, mesh)
            else:
                params_g = jax.device_put(
                    params, dp_group_devices(g, 1)[0])
            kw = dict(service_kw or {})
            if service_kw_fn is not None:
                kw.update(service_kw_fn(g) or {})
            engines.append(service_cls.from_model(
                model_g, params_g, tokenizer, **kw))
            logger.info("dp group %d/%d ready (tp=%d)", g + 1, dp, tp)
        return cls(engines, load_spread=load_spread)

    @classmethod
    def build_from_config(cls, config, service_cls, use_ema: bool = False,
                          dp: int = 2, tp: int = 1,
                          load_spread: float = 4.0,
                          service_kw=None, service_kw_fn=None):
        """The serve.py entry: one checkpoint/artifact restore, then
        ``dp`` group engines around re-placed copies of it."""
        from ..config.registry import MODELS
        from ..models.base import inject_mesh
        from .serving import load_generation_stack

        _, params, tok = load_generation_stack(
            config, use_ema=use_ema,
            tensor_parallel=(tp if int(tp) > 1 else 0))

        def factory(mesh):
            return inject_mesh(config.init_obj("arch", MODELS), mesh)

        return cls.from_model_factory(
            factory, params, dp, tp, service_cls, tokenizer=tok,
            load_spread=load_spread, service_kw=service_kw,
            service_kw_fn=service_kw_fn)

    # -- placement ----------------------------------------------------------

    def _loads(self):
        return [e.queue_depth() + e.live_slots()
                if hasattr(e, "queue_depth") else 0
                for e in self._engines]

    def _pick(self, ids=None) -> int:
        """Cache-aware group choice, the fleet chooser's in-process
        twin: deepest cached prefix wins unless that group's load
        exceeds the least-loaded's by more than the spread (a hot
        prefix must not hotspot one group while siblings idle);
        no match = least-loaded, ties rotate."""
        with self._lock:
            rr = self._rr
            self._rr += 1
        loads = self._loads()
        least = min(loads)
        tied = [i for i, l in enumerate(loads) if l <= least]
        least_i = tied[rr % len(tied)]
        if ids:
            best_i, best_c = None, 0
            for i, e in enumerate(self._engines):
                pf = getattr(e, "_prefix", None)
                if pf is None:
                    continue
                c = pf.cached_block_count(ids)
                if c > best_c:
                    best_c, best_i = c, i
            if best_i is not None and loads[best_i] - least <= self._spread:
                return best_i
        return least_i

    # -- the service surface ------------------------------------------------

    def generate(self, prompt=None, prompt_ids=None, **kw) -> dict:
        try:
            ids = self._engines[0].encode_prompt(prompt, prompt_ids)
        except ValueError:
            ids = None        # the group engine raises the real 400
        g = self._pick(ids)
        return self._engines[g].generate(
            prompt=prompt, prompt_ids=prompt_ids, **kw)

    def prefill_export(self, prompt=None, prompt_ids=None, **kw) -> dict:
        try:
            ids = self._engines[0].encode_prompt(prompt, prompt_ids)
        except ValueError:
            ids = None
        g = self._pick(ids)
        return self._engines[g].prefill_export(
            prompt=prompt, prompt_ids=prompt_ids, **kw)

    def export_cached_pages(self, prompt=None, prompt_ids=None,
                            **kw) -> dict:
        """Export-only peer migration (ISSUE 13): pick PURELY by who
        holds the deepest chain — an export is a read, and generate's
        load-gated pick would divert it to an idle group with an
        empty pool (n_blocks 0 while the pages sit one group over)."""
        try:
            ids = self._engines[0].encode_prompt(prompt, prompt_ids)
        except ValueError:
            ids = None          # group 0 raises the real 400 below
        g = 0
        if ids is not None:
            depths = [(e._prefix.cached_block_count(ids)
                       if e._prefix is not None else 0)
                      for e in self._engines]
            g = max(range(len(depths)), key=lambda i: depths[i])
        return self._engines[g].export_cached_pages(
            prompt=prompt, prompt_ids=prompt_ids, **kw)

    def import_remote_pages(self, payload) -> dict:
        """Land shipped pages on the least-loaded group's pool; the
        follow-up ``generate`` finds them through the same radix probe
        that placed them — the import is its own affinity record."""
        g = self._pick(None)
        receipt = self._engines[g].import_remote_pages(payload)
        receipt["dp_group"] = g
        return receipt

    def validate_request(self, req: dict) -> None:
        self._engines[0].validate_request(req)

    def encode_prompt(self, prompt=None, prompt_ids=None):
        return self._engines[0].encode_prompt(prompt, prompt_ids)

    def encode_stop(self, stop):
        return self._engines[0].encode_stop(stop)

    # -- observability ------------------------------------------------------

    @property
    def stats(self) -> dict:
        merged: dict = {"dp_groups": self.dp}
        for e in self._engines:
            for k, v in (getattr(e, "stats", None) or {}).items():
                if isinstance(v, bool):
                    continue
                if isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0) + v
        for k, v in self._own_stats.items():
            if isinstance(v, (int, float)):
                merged[k] = merged.get(k, 0) + v
            else:
                merged[k] = v
        return _StatsView(merged, self._own_stats)

    def queue_depth(self) -> int:
        return sum(e.queue_depth() for e in self._engines
                   if hasattr(e, "queue_depth"))

    def live_slots(self) -> int:
        return sum(e.live_slots() for e in self._engines
                   if hasattr(e, "live_slots"))

    def latency_percentiles(self) -> dict:
        lats = sorted(
            x for e in self._engines
            for x in list(getattr(e, "_latencies", ()))[-1024:])
        if not lats:
            return {}
        out = {"p50_s": round(percentile(lats, 0.5), 4),
               "p95_s": round(percentile(lats, 0.95), 4),
               "p99_s": round(percentile(lats, 0.99), 4),
               "n": len(lats)}
        ttfts = sorted(
            x for e in self._engines
            for x in list(getattr(e, "_ttfts", ()))[-1024:])
        if ttfts:
            out.update(
                ttft_p50_s=round(percentile(ttfts, 0.5), 4),
                ttft_p95_s=round(percentile(ttfts, 0.95), 4),
                ttft_p99_s=round(percentile(ttfts, 0.99), 4))
        return out

    @property
    def hist(self) -> dict:
        base = getattr(self._engines[0], "hist", None) or {}
        return {k: _MergedHist([e.hist[k] for e in self._engines])
                for k in base}

    def prefix_cache_stats(self):
        snaps = [s for s in (e.prefix_cache_stats()
                             for e in self._engines) if s]
        if not snaps:
            return None
        out: dict = {}
        for k, v0 in snaps[0].items():
            if isinstance(v0, bool):
                out[k] = all(s.get(k, False) for s in snaps)
            elif isinstance(v0, (int, float)):
                out[k] = sum(s.get(k, 0) for s in snaps)
            else:
                out[k] = v0
        lk = out.get("prefix_lookups", 0)
        out["prefix_hit_rate"] = round(
            out.get("prefix_hit_requests", 0) / lk, 4) if lk else 0.0
        return out

    def tp_stats(self) -> dict:
        # identical geometry per group: group 0 speaks for all — the
        # per-step collective accounting is a property of the program,
        # not of which group runs it
        return self._engines[0].tp_stats()

    def slo_stats(self) -> dict:
        # the SLO watcher is one shared object across groups
        return self._engines[0].slo_stats()

    @property
    def brownout_level(self) -> int:
        return max((getattr(e, "brownout_level", 0)
                    for e in self._engines), default=0)

    def brownout_stats(self) -> dict:
        stats = [e.brownout_stats() for e in self._engines
                 if hasattr(e, "brownout_stats")]
        if not stats:
            return {"brownout_level": 0}
        worst = max(stats,
                    key=lambda s: int(s.get("brownout_level", 0)))
        out = dict(worst)
        out["brownout_level"] = max(
            int(s.get("brownout_level", 0)) for s in stats)
        return out
