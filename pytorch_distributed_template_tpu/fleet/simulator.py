"""Time-compressed fleet simulator: the autoscaler's offline twin
(ISSUE 19).

A deterministic discrete-event engine that replays :mod:`.loadgen`
traces against the *measured* per-segment service-time distributions
PR 14 froze into ``service_model.json`` — virtual replicas with
queues, admission, brownout, warm/cold start costs, and scale events.
Virtual time costs nothing: a diurnal day compresses to however fast
the event loop runs, so policies and SLO budgets are validated at
request scales this container can't run live. The policy interface is
:mod:`.autoscaler`'s — the SAME :class:`AutoscalePolicy` instance
class drives both worlds (a sim-vs-live comparison on a real fleet has
no test yet: ROADMAP D11).

Determinism contract (pinned by tests/test_autoscale.py): same trace
+ same model + same seed ⇒ byte-identical event log and request rows.
Everything random flows through one ``random.Random(f"sim:{seed}")``
whose draw order is fixed by the event order, and ties in the event
heap break on a monotone sequence number — never on wall clock.

What the sampler does with the model: each segment entry carries the
shared log-histogram (body) plus exact measured quantiles
(p50/p90/p99/max). Draws below the median walk the histogram
(log-uniform inside a bin); draws above interpolate geometrically
between the exact anchors — so the simulated distribution's upper
tail converges to the measured p99 rather than to a bin edge, which
is what makes a 15% p99 validation gate meaningful at 8 bins/decade.

Stdlib-only, importable without jax (it simulates serve.py, it never
runs one).
"""
from __future__ import annotations

import dataclasses
import heapq
import json
import random
from typing import Dict, List, Optional

from ..observability.servicedist import (
    LOG_EDGES_S, prompt_len_bucket,
)
from ..utils.promtext import percentile as _percentile
from .autoscaler import (
    AutoscaleConfig, AutoscalePolicy, FleetSignals, SignalTracker,
    StaticPolicy,
)

__all__ = ["SimConfig", "ServiceSampler", "FleetSimulator",
           "simulate", "synthetic_model", "validate"]

#: segments sampled into the pre-first-token overhead, in stitch
#: order. ``admission_wait`` is deliberately ABSENT: the engine
#: models fleet-level slot queueing itself — sampling the live run's
#: admission queue on top would double-count it. ``scheduler_queue``
#: IS sampled: measured fleets show it is dominated by the engine's
#: batching-tick cadence — a dispatch floor every request pays even
#: on an idle replica (tight distribution, not load-shaped), which
#: the event engine does not otherwise model. Its contention share
#: does overlap the sim's own queueing at saturation, making the sim
#: conservative there; the validation arm (peak-provisioned static)
#: runs far from saturation, where the cadence reading is exact.
PREFLIGHT_SEGMENTS = ("router_recv", "route", "proxy_send",
                      "replica_recv", "scheduler_queue")
#: segments sampled into the post-last-token tail (e2e - decode end)
TAIL_SEGMENTS = ("stream", "proxy_return", "router_send")


def synthetic_model(prefill_cold_s: float = 0.12,
                    prefill_warm_s: float = 0.015,
                    decode_s: float = 0.16,
                    overhead_s: float = 0.004,
                    spread: float = 0.6, n: int = 101) -> dict:
    """A stand-in ``service_model.json`` for model-free runs (the CI
    policy sweep): every segment gets a deterministic log-spread
    sample set around its center, shaped EXACTLY like the measured
    model so the sampler takes one code path."""
    from ..observability.servicedist import _seg_stats

    def vals(center: float) -> List[float]:
        lo, hi = center * (1.0 - spread), center * (1.0 + spread)
        return [lo + (hi - lo) * i / (n - 1) for i in range(n)]

    def entry(center: float) -> dict:
        e = _seg_stats(vals(center))
        e["classes"] = {}
        return e

    admit = _seg_stats(vals(prefill_cold_s))
    admit["classes"] = {
        "cold|any|b0": _seg_stats(vals(prefill_cold_s)),
        "warm|any|b0": _seg_stats(vals(prefill_warm_s)),
    }
    return {
        "version": 1, "edges_s": list(LOG_EDGES_S),
        "segments": {
            "admit": admit,
            "decode": entry(decode_s),
            "router_recv": entry(overhead_s),
            "route": entry(overhead_s),
            "proxy_send": entry(overhead_s),
            "replica_recv": entry(overhead_s),
            "stream": entry(overhead_s),
        },
    }


class ServiceSampler:
    """Draws per-request segment times from a service model."""

    def __init__(self, model: Optional[dict] = None,
                 rng: Optional[random.Random] = None):
        self.model = model or synthetic_model()
        self.rng = rng or random.Random("sim:sampler")
        self.edges = list(self.model.get("edges_s") or LOG_EDGES_S)
        self.segments = dict(self.model.get("segments") or {})

    # -- one entry -----------------------------------------------------------

    @staticmethod
    def _interp(lo: float, hi: float, f: float) -> float:
        if lo > 0.0 and hi > 0.0:
            return lo * (hi / lo) ** f
        return lo + (hi - lo) * f

    def _hist_value(self, entry: dict, u: float) -> float:
        """Body draw: the value at quantile ``u`` of the histogram,
        log-uniform inside the landing bin."""
        counts = entry.get("hist_counts") or []
        total = sum(counts)
        if total <= 0:
            return float(entry.get("p50_s", 0.0))
        target = u * total
        acc = 0.0
        idx = len(counts) - 1
        for i, c in enumerate(counts):
            if acc + c >= target and c > 0:
                idx = i
                break
            acc += c
        frac = min(max((target - acc) / max(counts[idx], 1), 0.0), 1.0)
        edges = self.edges
        if idx == 0:
            lo, hi = edges[0] / 10.0, edges[0]
        elif idx >= len(edges):
            lo, hi = edges[-1], float(entry.get("max_s", edges[-1]))
        else:
            lo, hi = edges[idx - 1], edges[idx]
        return self._interp(lo, max(hi, lo), frac)

    def sample_entry(self, entry: dict) -> float:
        """One draw from one ``_seg_stats`` entry: histogram body
        below the median, exact-quantile anchors above it."""
        u = self.rng.random()
        p50 = float(entry.get("p50_s", 0.0))
        p90 = float(entry.get("p90_s", p50))
        p99 = float(entry.get("p99_s", p90))
        mx = float(entry.get("max_s", p99))
        if u < 0.50:
            return min(self._hist_value(entry, u), p50)
        if u < 0.90:
            return self._interp(p50, p90, (u - 0.50) / 0.40)
        if u < 0.99:
            return self._interp(p90, p99, (u - 0.90) / 0.09)
        return self._interp(p99, mx, (u - 0.99) / 0.01)

    # -- segment lookup ------------------------------------------------------

    def _entry(self, name: str, cls: Optional[str] = None
               ) -> Optional[dict]:
        seg = self.segments.get(name)
        if seg is None:
            return None
        classes = seg.get("classes") or {}
        if cls is not None:
            if cls in classes:
                return classes[cls]
            mode = cls.split("|", 1)[0]
            pooled = [e for k, e in sorted(classes.items())
                      if k.startswith(mode + "|")]
            if pooled:
                # merge-by-best-count: the largest matching class is
                # the least noisy stand-in for a missing exact class
                return max(pooled, key=lambda e: e.get("count", 0))
        return seg

    def admit_s(self, warm: bool, prompt_tokens: int,
                stream: bool) -> float:
        mode = "warm" if warm else "cold"
        cls = (f"{mode}|{'stream' if stream else 'unary'}"
               f"|b{prompt_len_bucket(prompt_tokens)}")
        entry = self._entry("admit", cls)
        if entry is None:
            return 0.05 if warm else 0.2
        return self.sample_entry(entry)

    def decode_s(self, new_tokens: int) -> float:
        entry = self._entry("decode")
        if entry is None:
            return 0.02 * max(int(new_tokens), 1)
        return self.sample_entry(entry)

    def overhead_s(self) -> float:
        return sum(self.sample_entry(e) for e in
                   (self._entry(n) for n in PREFLIGHT_SEGMENTS)
                   if e is not None)

    def tail_s(self) -> float:
        return sum(self.sample_entry(e) for e in
                   (self._entry(n) for n in TAIL_SEGMENTS)
                   if e is not None)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    slots_per_replica: int = 4
    queue_factor: float = 2.0      #: admission oversubscription
    max_waiting: int = 256         #: waiting-room bound, shed beyond
    tick_s: float = 1.0            #: policy cadence (virtual seconds)
    #: supervised start -> READY: cold, and with the warm-signature
    #: ladder + shared compile cache (PR 9's 0.47 s first-request fix
    #: is what makes the warm figure real)
    cold_spawn_s: float = 12.0
    warm_spawn_s: float = 3.0
    #: pre-load scale-up spawns with the fleet's hottest prefix
    #: groups (the live actuator's PR 13 re-warm pull)
    rewarm_on_spawn: bool = True
    rewarm_top_k: int = 8
    #: fleet-wide backlog/slot ratios entering brownout levels 1..n
    #: (instantaneous variant of utils.brownout for the signal feed)
    brownout_enter: tuple = (1.0, 2.0, 4.0)
    slo_ttft_s: Optional[float] = None
    slo_e2e_s: Optional[float] = None


class _SimReplica:
    __slots__ = ("rid", "role", "state", "ready_at", "spawned_at",
                 "removed_at", "queue", "active", "warm_groups",
                 "warm_spawn")

    def __init__(self, rid: str, t: float, ready_at: float,
                 role: str = "both"):
        self.rid = rid
        self.role = role
        self.state = "starting"       # starting|healthy|draining
        self.spawned_at = t
        self.ready_at = ready_at
        self.removed_at: Optional[float] = None
        self.queue: List[dict] = []
        self.active: List[dict] = []
        self.warm_groups: set = set()
        self.warm_spawn = False

    def load(self) -> int:
        return len(self.queue) + len(self.active)


class FleetSimulator:
    """The discrete-event engine. One instance = one run."""

    def __init__(self, trace: List[dict], policy,
                 model: Optional[dict] = None,
                 cfg: SimConfig = SimConfig(),
                 initial_replicas: int = 2, seed: int = 0):
        self.trace = list(trace)
        self.policy = policy
        self.cfg = cfg
        self.rng = random.Random(f"sim:{seed}")
        self.sampler = ServiceSampler(model, rng=self.rng)
        self.tracker = SignalTracker()
        self.t = 0.0
        self._seq = 0
        self._heap: List[tuple] = []
        self.replicas: Dict[str, _SimReplica] = {}
        self.retired: List[_SimReplica] = []
        self.waiting: List[dict] = []
        self.events: List[dict] = []
        self.requests: List[dict] = []
        self.group_last_use: Dict[str, float] = {}
        self.arrivals = 0
        self.breaches = 0
        self.sheds = 0
        self.scale_ups = self.scale_downs = self.role_flips = 0
        self._spawn_idx = 0
        self._pending_flips: List[tuple] = []
        self._peak = self._floor = initial_replicas
        for i in range(initial_replicas):
            r = _SimReplica(f"r{i}", 0.0, 0.0)
            r.state = "healthy"
            self.replicas[r.rid] = r

    # -- plumbing ------------------------------------------------------------

    def _push(self, t: float, kind: str, data: dict) -> None:
        self._seq += 1
        heapq.heappush(self._heap, (t, self._seq, kind, data))

    def _log(self, ev: str, **kw) -> None:
        row = {"t": round(self.t, 6), "ev": ev}
        row.update(kw)
        self.events.append(row)

    def _healthy(self) -> List[_SimReplica]:
        return [r for r in self.replicas.values()
                if r.state == "healthy"]

    def _brownout_level(self) -> int:
        healthy = self._healthy()
        slots = max(sum(self.cfg.slots_per_replica for _ in healthy),
                    1)
        backlog = (sum(r.load() for r in healthy) + len(self.waiting))
        ratio = backlog / slots
        level = 0
        for thr in self.cfg.brownout_enter:
            if ratio >= thr:
                level += 1
        return level

    # -- request flow --------------------------------------------------------

    def _capacity(self) -> int:
        return int(sum(self.cfg.slots_per_replica
                       for _ in self._healthy())
                   * self.cfg.queue_factor)

    def _on_arrival(self, item: dict) -> None:
        self.arrivals += 1
        outstanding = (len(self.waiting)
                       + sum(r.load() for r in self.replicas.values()))
        if (outstanding >= self._capacity()
                and len(self.waiting) >= self.cfg.max_waiting):
            self.sheds += 1
            self._log("shed", rid=item.get("rid"))
            self.requests.append({
                "rid": item.get("rid"), "ok": False, "shed": True,
                "t": round(self.t, 6)})
            return
        item = dict(item)
        item["_arrived"] = self.t
        self.waiting.append(item)
        self._dispatch()

    def _dispatch(self) -> None:
        """Route every admissible waiting request: warm-affinity
        first (the cache-aware policy), least-loaded fallback, bounded
        per-replica queues via the capacity oversubscription."""
        while self.waiting:
            healthy = self._healthy()
            if not healthy:
                return
            total_load = sum(r.load() for r in healthy)
            if total_load >= self._capacity():
                return
            item = self.waiting.pop(0)
            group = item.get("group")
            by_load = sorted(healthy,
                             key=lambda r: (r.load(), r.rid))
            min_load = by_load[0].load()
            pick = None
            for r in by_load:
                if (group in r.warm_groups
                        and r.load() <= min_load + 4.0):
                    pick = r
                    break
            if pick is None:
                pick = by_load[0]
            pick.queue.append(item)
            self._serve(pick)

    def _serve(self, r: _SimReplica) -> None:
        while (r.queue
               and len(r.active) < self.cfg.slots_per_replica):
            item = r.queue.pop(0)
            group = item.get("group")
            warm = group in r.warm_groups
            r.warm_groups.add(group)
            self.group_last_use[group] = self.t
            prompt = len(item.get("prompt_ids") or ())
            tokens = int(item.get("max_new_tokens", 1))
            stream = bool(item.get("stream"))
            oh = self.sampler.overhead_s()
            admit = self.sampler.admit_s(warm, prompt, stream)
            decode = self.sampler.decode_s(tokens)
            tail = self.sampler.tail_s()
            item["_warm"] = warm
            item["_ttft"] = (self.t - item["_arrived"]) + oh + admit
            item["_tpot"] = decode / max(tokens - 1, 1)
            item["_e2e"] = ((self.t - item["_arrived"])
                            + oh + admit + decode + tail)
            item["_tokens"] = tokens
            r.active.append(item)
            self._push(self.t + oh + admit + decode, "finish",
                       {"rid": r.rid, "item": item})

    def _on_finish(self, r: _SimReplica, item: dict) -> None:
        if item in r.active:
            r.active.remove(item)
        cfg = self.cfg
        breach = ((cfg.slo_ttft_s is not None
                   and item["_ttft"] > cfg.slo_ttft_s)
                  or (cfg.slo_e2e_s is not None
                      and item["_e2e"] > cfg.slo_e2e_s))
        if breach:
            self.breaches += 1
        self.requests.append({
            "rid": item.get("rid"), "ok": True, "shed": False,
            "warm": item["_warm"], "tokens": item["_tokens"],
            "ttft_s": round(item["_ttft"], 6),
            "tpot_s": round(item["_tpot"], 6),
            "e2e_s": round(item["_e2e"], 6),
            "breach": breach})
        self._serve(r)
        self._dispatch()
        if (r.state == "draining" and not r.queue and not r.active):
            self._remove_now(r)

    # -- scale actuation -----------------------------------------------------

    def _fleet_hot_groups(self) -> List[str]:
        hot = sorted(self.group_last_use.items(),
                     key=lambda kv: (-kv[1], kv[0]))
        return [g for g, _ in hot[:self.cfg.rewarm_top_k]]

    def _spawn(self, role: str = "both") -> str:
        rid = f"s{self._spawn_idx}"
        self._spawn_idx += 1
        warm = self.cfg.rewarm_on_spawn
        delay = (self.cfg.warm_spawn_s if warm
                 else self.cfg.cold_spawn_s)
        r = _SimReplica(rid, self.t, self.t + delay, role=role)
        r.warm_spawn = warm
        self.replicas[rid] = r
        self._push(r.ready_at, "ready", {"rid": rid})
        self._log("spawn", rid=rid, role=role,
                  ready_at=round(r.ready_at, 6), warm=warm)
        return rid

    def _on_ready(self, r: _SimReplica) -> None:
        if r.state != "starting":
            return
        r.state = "healthy"
        if r.warm_spawn:
            # the PR 13 pull path replayed the fleet's hottest chains
            # into the spawn before readmission: it opens warm
            r.warm_groups.update(self._fleet_hot_groups())
        self._log("ready", rid=r.rid,
                  warm_groups=len(r.warm_groups))
        self._peak = max(self._peak, len(self.replicas))
        self._dispatch()
        self._settle_flips()

    def _remove_now(self, r: _SimReplica) -> None:
        r.removed_at = self.t
        self.replicas.pop(r.rid, None)
        self.retired.append(r)
        self._log("removed", rid=r.rid)
        self._floor = min(self._floor, len(self.replicas))
        self._dispatch()

    def _drain(self, rid: str) -> bool:
        r = self.replicas.get(rid)
        if r is None or r.state == "draining":
            return False
        # re-queue its unstarted work fleet-wide, finish the active
        for item in r.queue:
            self.waiting.insert(0, item)
        r.queue = []
        r.state = "draining"
        self._log("drain", rid=rid)
        if not r.active:
            self._remove_now(r)
        else:
            self._dispatch()
        return True

    def _settle_flips(self) -> None:
        for new_rid, old_rid in list(self._pending_flips):
            rep = self.replicas.get(new_rid)
            if rep is None:
                self._pending_flips.remove((new_rid, old_rid))
            elif rep.state == "healthy":
                self._drain(old_rid)
                self.role_flips += 1
                self._pending_flips.remove((new_rid, old_rid))

    def _apply(self, act: dict) -> None:
        op = act.get("op")
        if op == "scale_up":
            for _ in range(int(act.get("n", 1))):
                self._spawn()
                self.scale_ups += 1
            self._log("scale_up", n=int(act.get("n", 1)),
                      reason=act.get("reason"),
                      pressure=act.get("pressure"))
        elif op == "scale_down":
            if self._drain(act.get("rid")):
                self.scale_downs += 1
                self._log("scale_down", rid=act.get("rid"),
                          reason=act.get("reason"),
                          pressure=act.get("pressure"))
        elif op == "role_flip":
            new_rid = self._spawn(role=act.get("role", "both"))
            self._pending_flips.append((new_rid, act.get("rid")))
            self._log("role_flip", rid=act.get("rid"),
                      replacement=new_rid, role=act.get("role"))

    # -- the policy tick -----------------------------------------------------

    def _signals(self) -> FleetSignals:
        healthy = self._healthy()
        slots = float(sum(self.cfg.slots_per_replica
                          for _ in healthy))
        self.tracker.update(self.t, {
            "arrivals": float(self.arrivals),
            "breaches": float(self.breaches)})
        loads = {r.rid: float(r.load()) for r in healthy}
        roles = {r.rid: r.role for r in healthy}
        prefill_tokens = active_tokens = 0.0
        for r in healthy:
            for item in r.active + r.queue:
                p = float(len(item.get("prompt_ids") or ()))
                d = float(item.get("max_new_tokens", 1))
                prefill_tokens += p
                active_tokens += p + d
        share = (prefill_tokens / active_tokens
                 if active_tokens > 0 else 0.0)
        return FleetSignals(
            t=self.t, replicas=len(self.replicas),
            healthy=len(healthy), slots=slots,
            queue_depth=float(len(self.waiting)
                              + sum(len(r.queue) for r in healthy)),
            inflight=float(sum(len(r.active) for r in healthy)),
            brownout_level=self._brownout_level(),
            slo_breach_rate=self.tracker.rate("breaches"),
            arrival_rate=self.tracker.rate("arrivals"),
            arrival_trend=self.tracker.trend("arrivals"),
            avg_service_s=0.0,
            prefill_share=share,
            replica_loads=loads, replica_roles=roles)

    def _on_tick(self) -> None:
        self._settle_flips()
        for act in self.policy.decide(self._signals()):
            self._apply(act)

    # -- run -----------------------------------------------------------------

    def run(self) -> dict:
        for item in self.trace:
            self._push(float(item["t"]), "arrival", {"item": item})
        horizon = (float(self.trace[-1]["t"]) if self.trace else 0.0)
        tick_t = self.cfg.tick_s
        while tick_t <= horizon:
            self._push(tick_t, "tick", {})
            tick_t += self.cfg.tick_s
        while self._heap:
            t, _, kind, data = heapq.heappop(self._heap)
            self.t = t
            if kind == "arrival":
                self._on_arrival(data["item"])
            elif kind == "finish":
                r = (self.replicas.get(data["rid"])
                     or next((x for x in self.retired
                              if x.rid == data["rid"]), None))
                if r is not None:
                    self._on_finish(r, data["item"])
            elif kind == "ready":
                r = self.replicas.get(data["rid"])
                if r is not None:
                    self._on_ready(r)
            elif kind == "tick":
                self._on_tick()
        # the ledger closes at the last event's virtual time
        return self.summary()

    # -- output --------------------------------------------------------------

    def replica_seconds(self) -> float:
        end = self.t
        total = 0.0
        for r in list(self.replicas.values()) + self.retired:
            stop = r.removed_at if r.removed_at is not None else end
            total += max(stop - r.spawned_at, 0.0)
        return total

    def summary(self) -> dict:
        ok = [r for r in self.requests if r.get("ok")]
        ttft = sorted(r["ttft_s"] for r in ok)
        tpot = sorted(r["tpot_s"] for r in ok
                      if r.get("tokens", 0) > 1)
        e2e = sorted(r["e2e_s"] for r in ok)
        out = {
            "requests": len(self.requests),
            "ok": len(ok),
            "shed": self.sheds,
            "failed": len(self.requests) - len(ok) - self.sheds,
            "breaches": self.breaches,
            "slo_compliant_frac": (round(
                1.0 - self.breaches / len(ok), 6) if ok else None),
            "duration_s": round(self.t, 6),
            "replica_seconds": round(self.replica_seconds(), 3),
            "peak_replicas": self._peak,
            "floor_replicas": self._floor,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "role_flips": self.role_flips,
        }
        for name, vals in (("ttft", ttft), ("tpot", tpot),
                           ("e2e", e2e)):
            out[f"{name}_p50_s"] = (round(_percentile(vals, 0.50), 6)
                                    if vals else None)
            out[f"{name}_p99_s"] = (round(_percentile(vals, 0.99), 6)
                                    if vals else None)
        return out


def simulate(trace: List[dict], policy,
             model: Optional[dict] = None,
             cfg: SimConfig = SimConfig(),
             initial_replicas: int = 2, seed: int = 0) -> dict:
    """One run; returns ``{"summary", "events", "requests"}``."""
    sim = FleetSimulator(trace, policy, model=model, cfg=cfg,
                         initial_replicas=initial_replicas, seed=seed)
    summary = sim.run()
    return {"summary": summary, "events": sim.events,
            "requests": sim.requests}


def validate(sim_summary: dict, live_summary: dict,
             keys=(("ttft_p99_s", "ttft_p99_s"),
                   ("tpot_p99_s", "tpot_p99_s")),
             tol: float = 0.15,
             abs_floor_s: float = 0.0) -> dict:
    """The simulator-vs-live contract (docs/FLEET.md): relative error
    per metric pair, and whether every comparable pair is within
    ``tol``. A pair with a missing side is reported but not gated
    (e.g. a run with too few streaming samples has no live TPOT).

    ``abs_floor_s`` exempts pairs whose ABSOLUTE gap is below it:
    at sub-millisecond per-token times on a CPU dev fleet a 15%
    relative band is narrower than timer/scheduling jitter, so a
    small floor keeps the gate honest there while leaving real-scale
    latencies (where the gap dwarfs any floor) on the pure relative
    contract. The floor used is recorded in the result."""
    out = {"tol": tol, "abs_floor_s": abs_floor_s,
           "metrics": {}, "ok": True, "compared": 0}
    for sim_key, live_key in keys:
        s, lv = sim_summary.get(sim_key), live_summary.get(live_key)
        if s is None or lv is None or not lv:
            out["metrics"][sim_key] = {"sim": s, "live": lv,
                                       "rel_err": None}
            continue
        gap = abs(float(s) - float(lv))
        rel = gap / float(lv)
        out["metrics"][sim_key] = {"sim": round(float(s), 6),
                                   "live": round(float(lv), 6),
                                   "rel_err": round(rel, 4),
                                   "abs_err_s": round(gap, 6)}
        out["compared"] += 1
        if rel > tol and gap > abs_floor_s:
            out["ok"] = False
    return out


def main(argv=None) -> int:
    import argparse

    from .loadgen import diurnal_trace

    p = argparse.ArgumentParser(
        description="deterministic fleet simulator: replay a diurnal "
                    "loadgen trace against a measured service model "
                    "under an autoscale or static policy")
    p.add_argument("--n", type=int, default=400)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--peak-rps", type=float, default=6.0)
    p.add_argument("--period-s", type=float, default=60.0)
    p.add_argument("--floor", type=float, default=0.1)
    p.add_argument("--sharpness", type=int, default=3)
    p.add_argument("--model", default=None,
                   help="service_model.json path (absent: synthetic)")
    p.add_argument("--policy", default="autoscale",
                   choices=("autoscale", "static"))
    p.add_argument("--replicas", type=int, default=2,
                   help="initial (static: fixed) replica count")
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--max-replicas", type=int, default=4)
    p.add_argument("--slo-ttft-s", type=float, default=None)
    p.add_argument("--slo-e2e-s", type=float, default=None)
    p.add_argument("--sweep", action="store_true",
                   help="run BOTH arms (static peak vs autoscale) on "
                        "one trace and report the replica-seconds "
                        "saving — the CI policy-sweep gate")
    p.add_argument("--events", action="store_true",
                   help="include the event log in the JSON")
    args = p.parse_args(argv)

    model = None
    if args.model:
        with open(args.model, "r", encoding="utf-8") as fh:
            model = json.load(fh)
    trace = diurnal_trace(args.n, seed=args.seed,
                          peak_rps=args.peak_rps,
                          period_s=args.period_s, floor=args.floor,
                          sharpness=args.sharpness)
    cfg = SimConfig(slo_ttft_s=args.slo_ttft_s,
                    slo_e2e_s=args.slo_e2e_s)

    def run(policy, n0):
        return simulate(trace, policy, model=model, cfg=cfg,
                        initial_replicas=n0, seed=args.seed)

    if args.sweep:
        static = run(StaticPolicy(), args.max_replicas)
        auto = run(AutoscalePolicy(AutoscaleConfig(
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas)), args.replicas)
        rs_static = static["summary"]["replica_seconds"]
        rs_auto = auto["summary"]["replica_seconds"]
        saving = (1.0 - rs_auto / rs_static) if rs_static else 0.0
        out = {
            "static": static["summary"],
            "autoscaled": auto["summary"],
            "replica_seconds_saving": round(saving, 4),
        }
        print(json.dumps(out, indent=2))
        return 0
    policy = (StaticPolicy() if args.policy == "static"
              else AutoscalePolicy(AutoscaleConfig(
                  min_replicas=args.min_replicas,
                  max_replicas=args.max_replicas)))
    res = run(policy, args.replicas)
    out = {"summary": res["summary"]}
    if args.events:
        out["events"] = res["events"]
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
