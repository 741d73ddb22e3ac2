"""Flight recorder: one structured record per training step.

MegaScale-style per-step telemetry as a first-class subsystem: the
trainer feeds one record per step into a bounded
in-memory ring buffer, and process 0 appends each record as a JSON
line to ``<run_dir>/telemetry.jsonl``. A wedged or crashed run leaves
its last ``capacity`` steps on disk and in the watchdog's stall dump
(utils/watchdog.py) instead of evaporating; a healthy run leaves a
machine-parseable timeline that tooling (benchmarks/run.py, sweeps,
dashboards) reads back without scraping logs.

Record schema (all optional except ``v``/``step``/``t``):

    {"v": 1, "step": 0, "t": <unix seconds>,
     "wall_ms": ..., "data_wait_ms": ..., "dispatch_ms": ...,
     "unattributed_ms": ..., "record_ms": ..., "stall": {...},
     "loss": ..., "grad_norm": ..., "lr": ...,
     "examples": ..., "tokens": ...,
     "steps_per_sec": ..., "examples_per_sec": ..., "tokens_per_sec": ...,
     "mfu": ...,
     "compile_events": [{"event": ..., "fun_name": ..., "dur_ms": ...},
                        ...],
     "host_rss_mb": ..., "devices": {"0": {"bytes_in_use": ...,
                                           "peak_bytes_in_use": ...}}}

Memory fields attach every ``memory_every`` records (host RSS is a
/proc read, device HBM a ``memory_stats()`` call per device — cheap,
but not per-step cheap on big slices). Compile events come from a
``jax.monitoring`` duration listener installed once per process: any
jit/pjit compilation that happened since the previous record rides
along on the next one, so recompilation storms are visible in the
timeline instead of silently halving throughput, each under the name
of the function that compiled. ``IterationAccount`` closes a training
iteration's account (``unattributed_ms``) and names a stalled one
(``stall``).
"""
from __future__ import annotations

import atexit
import collections
import gc
import json
import os
import resource
import statistics
import threading
import time
import weakref
from pathlib import Path
from typing import Optional

SCHEMA_VERSION = 1

# File-backed recorders register here so ONE atexit hook fsyncs every
# live JSONL tail on interpreter exit — normal return, sys.exit, and
# unhandled exceptions all run atexit, so a crashing run keeps its last
# ring of records on disk without every caller remembering to flush().
# (os._exit and SIGKILL bypass atexit; the watchdog's stall-path flush
# covers the wedged-then-killed case.) WeakSet: registration must not
# keep closed recorders alive.
_live_recorders: "weakref.WeakSet" = weakref.WeakSet()
_atexit_lock = threading.Lock()
_atexit_installed = False


def _flush_live_recorders() -> None:
    for rec in list(_live_recorders):
        try:
            rec.flush()
        except Exception:  # noqa: BLE001 — exit hooks must never raise
            pass


def _register_for_atexit(recorder) -> None:
    global _atexit_installed
    _live_recorders.add(recorder)
    with _atexit_lock:
        if not _atexit_installed:
            _atexit_installed = True
            atexit.register(_flush_live_recorders)

# ---------------------------------------------------------------------------
# host / device memory probes
# ---------------------------------------------------------------------------


def host_rss_bytes() -> Optional[int]:
    """Resident set size of this process, or None when unknowable."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import sys

        # ru_maxrss is KB on linux, bytes on macOS; prefer /proc above,
        # this is the portable fallback (peak, not current)
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return rss if sys.platform == "darwin" else rss * 1024
    except Exception:
        return None


def device_memory_stats() -> dict:
    """Per-device HBM stats from ``Device.memory_stats()``.

    ``{device_index: {"bytes_in_use": ..., "peak_bytes_in_use": ...}}``;
    empty on backends that don't report (CPU returns None)."""
    out: dict = {}
    try:
        import jax

        for d in jax.local_devices():
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                pass
            if not stats:
                continue
            out[str(d.id)] = {
                k: int(v) for k, v in stats.items()
                if k in ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_limit", "num_allocs")
            }
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# compile-event capture (process-wide, installed once)
# ---------------------------------------------------------------------------

_compile_lock = threading.Lock()
_compile_events: "collections.deque" = collections.deque(maxlen=256)
_compile_listener_installed = False
# persistent-compilation-cache counters (utils/compile_cache wires the
# cache itself; these count process lifetime hits/misses/requests —
# /metrics reads them). A "miss" IS a real
# XLA compile; a "hit" is an executable deserialized from the cache dir.
_cache_counters = {"hits": 0, "misses": 0, "requests": 0}


# jaxpr_trace_duration fires for every traced sub-jaxpr, nested inside
# the function that called it: depth by thread tells the top-level one,
# whose duration holds theirs. One entry a function between two drains.
_trace_depth: dict = {}          # thread id -> open trace events
_trace_entries: dict = {}        # fun_name -> its entry in _compile_events


def _install_compile_listener() -> None:
    """Register ``jax.monitoring`` listeners recording every compilation
    event (durations, each with the ``fun_name`` jax passes) and every
    persistent-cache hit/miss (plain events). Idempotent; silently
    absent on jax builds without the monitoring API."""
    global _compile_listener_installed
    with _compile_lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True
    try:
        from jax import monitoring

        def _listen_start(event: str, value: float, **kw) -> None:
            # jax records a scalar (the start time) as each timed stage
            # begins; only tracing nests
            if event.endswith("jaxpr_trace_duration"):
                tid = threading.get_ident()
                with _compile_lock:
                    _trace_depth[tid] = _trace_depth.get(tid, 0) + 1

        def _listen(event: str, duration: float, **kw) -> None:
            # the stages of a compilation: tracing (top-level functions
            # only, summed by name: the sub-jaxprs' own events are
            # hundreds of sub-ms entries on a first step, and their
            # time is in their caller's), MLIR lowering, the backend's
            # compile or cache read, the cache's disk read. Dropped:
            # compile_time_saved_sec, an estimate and not a duration of
            # anything this process did
            if "compil" not in event or event.endswith("time_saved_sec"):
                return
            dur_ms = round(duration * 1e3, 3)
            # tracing says "train_step", the later stages the module's
            # name, "jit(train_step)": one name a function
            fun_name = kw.get("fun_name")
            if fun_name is not None:
                fun_name = str(fun_name)
                if fun_name.startswith("jit(") and fun_name.endswith(")"):
                    fun_name = fun_name[4:-1]
            is_trace = event.endswith("jaxpr_trace_duration")
            with _compile_lock:
                if is_trace:
                    tid = threading.get_ident()
                    depth = max(_trace_depth.pop(tid, 0) - 1, 0)
                    if depth:
                        _trace_depth[tid] = depth
                        return
                    entry = _trace_entries.get(fun_name)
                    if entry is not None:
                        entry["dur_ms"] = round(entry["dur_ms"] + dur_ms, 3)
                        return
                entry = {"event": event, "dur_ms": dur_ms}
                if fun_name is not None:
                    entry["fun_name"] = fun_name
                if is_trace:
                    _trace_entries[fun_name] = entry
                _compile_events.append(entry)

        def _listen_plain(event: str, **kw) -> None:
            # cache hit/miss ride the per-step records too (a miss next
            # to a backend_compile duration says the compile was real;
            # a hit says it was a disk read) — note the
            # backend_compile_duration event fires EITHER WAY in jax
            # (it wraps compile_or_get_cached), so these events are the
            # only honest new-compile signal when the cache is on
            if not event.startswith("/jax/compilation_cache/"):
                return
            key = event.rsplit("/", 1)[-1]
            with _compile_lock:
                if key == "cache_hits":
                    _cache_counters["hits"] += 1
                    _compile_events.append({"event": event})
                elif key == "cache_misses":
                    _cache_counters["misses"] += 1
                    _compile_events.append({"event": event})
                elif key == "compile_requests_use_cache":
                    _cache_counters["requests"] += 1

        monitoring.register_scalar_listener(_listen_start)
        monitoring.register_event_duration_secs_listener(_listen)
        monitoring.register_event_listener(_listen_plain)
    except Exception:
        pass


def drain_compile_events() -> list:
    """Compilation events since the last drain (process-wide)."""
    with _compile_lock:
        out = list(_compile_events)
        _compile_events.clear()
        _trace_entries.clear()
    return out


def compile_cache_stats() -> dict:
    """Process-lifetime persistent-compilation-cache counters.

    ``misses`` counts real XLA compiles (cache enabled but no entry),
    ``hits`` counts executables loaded from the cache dir instead of
    compiled. All zero when the cache was never enabled (the listener
    only sees events jax emits, and jax emits none without a cache
    dir). Consumer: serve.py ``GET /metrics``."""
    try:
        import jax

        cache_dir = jax.config.jax_compilation_cache_dir
    except Exception:
        cache_dir = None
    with _compile_lock:
        counters = dict(_cache_counters)
    return {
        "enabled": bool(cache_dir),
        "dir": cache_dir,
        **counters,
    }


# ---------------------------------------------------------------------------
# what the host was doing: garbage collections and the kernel's counts
# ---------------------------------------------------------------------------

_gc_lock = threading.Lock()
_gc_installed = False
_gc_totals = {"ms": 0.0, "gen2": 0, "t0": None}


def _on_gc(phase: str, info: dict) -> None:
    # runs under the interpreter lock, on the collecting thread, and
    # only when a collection does; every Python thread waits meanwhile
    if phase == "start":
        _gc_totals["t0"] = time.perf_counter()
    elif _gc_totals["t0"] is not None:
        _gc_totals["ms"] += (time.perf_counter() - _gc_totals["t0"]) * 1e3
        _gc_totals["t0"] = None
        if info.get("generation") == 2:
            _gc_totals["gen2"] += 1


def _install_gc_callback() -> None:
    global _gc_installed
    with _gc_lock:
        if not _gc_installed:
            _gc_installed = True
            gc.callbacks.append(_on_gc)


def host_counters() -> tuple:
    """``(gc_ms, gc_gen2, nvcsw, nivcsw, majflt)`` of this process so
    far: milliseconds inside garbage collections and how many were of
    generation 2 (counted from the first ``FlightRecorder`` on), and
    ``getrusage``'s voluntary and involuntary context switches and
    major page faults. Their difference over an iteration tells a late
    host from a late device."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return (_gc_totals["ms"], _gc_totals["gen2"],
            ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_majflt)


# the parts of an iteration that have a field of their own; what
# wall_ms holds beyond them is unattributed_ms
ITERATION_PARTS = ("data_wait_ms", "dispatch_ms", "health_fetch_ms",
                   "log_flush_ms", "profile_ms")


class IterationAccount:
    """Closes each training iteration's account and names a stalled one.

    ``settle(rec)`` adds ``unattributed_ms`` = ``wall_ms`` less the
    ``ITERATION_PARTS`` the record carries, so the parts sum to the
    whole on every record. A record whose ``wall_ms`` exceeds twice the
    median of the up to 32 before it (at least 8) also gains
    ``stall``::

        {"over_ms": wall_ms - that median,
         "in": the part that grew most beyond its own trailing median,
               or "unattributed",
         "gc_ms", "gc_gen2", "nvcsw", "nivcsw", "majflt":
               ``host_counters()`` over the iteration,
         "threads": the other threads' open spans, by name}

    A late device shows in ``dispatch_ms``, ``health_fetch_ms`` or
    ``log_flush_ms`` (the host blocked on the chip); a profiler
    capture's start or stop in ``profile_ms``; a late host in none of
    the parts, with a collection, involuntary switches or page faults
    beside it (where the kernel counts them: a sandboxed one may say 0
    throughout). The rule has no knob. Call ``settle`` once an
    iteration, where its clock is read: the counters' difference runs
    from one call to the next.
    """

    WINDOW, AT_LEAST, TIMES = 32, 8, 2.0

    def __init__(self, spans=None):
        self.spans = spans          # a SpanRecorder, for ``threads``
        self._recent: "collections.deque" = collections.deque(
            maxlen=self.WINDOW)
        self._counters = host_counters()

    def settle(self, rec: dict, first: bool = False) -> Optional[dict]:
        """Complete ``rec`` in place; the ``stall`` it gained, or None.
        ``first``: the run's first iteration, which waits for the
        compile: never a stall, and kept out of the trailing window."""
        parts = {k: rec.get(k, 0.0) for k in ITERATION_PARTS}
        rec["unattributed_ms"] = round(
            rec["wall_ms"] - sum(parts.values()), 3)
        parts["unattributed_ms"] = rec["unattributed_ms"]
        counters, before = host_counters(), self._counters
        self._counters = counters
        if first:
            return None
        recent = list(self._recent)
        self._recent.append((rec["wall_ms"], parts))
        if len(recent) < self.AT_LEAST:
            return None
        typical = statistics.median(w for w, _ in recent)
        if rec["wall_ms"] <= self.TIMES * typical:
            return None
        grew = {k: v - statistics.median(p[k] for _, p in recent)
                for k, v in parts.items()}
        worst = max(grew, key=grew.get)
        me = threading.get_ident()
        stall = {
            "over_ms": round(rec["wall_ms"] - typical, 3),
            "in": "unattributed" if worst == "unattributed_ms" else worst,
            "gc_ms": round(counters[0] - before[0], 3),
            "gc_gen2": counters[1] - before[1],
            "nvcsw": counters[2] - before[2],
            "nivcsw": counters[3] - before[3],
            "majflt": counters[4] - before[4],
            "threads": sorted({s["name"]
                               for s in self.spans.active_spans()
                               if s["tid"] != me})
            if self.spans is not None else [],
        }
        rec["stall"] = stall
        return stall


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded per-step record ring + JSONL writer.

    :param run_dir: directory for ``telemetry.jsonl``; None disables the
        file (ring buffer only — e.g. non-main processes, tests).
    :param capacity: ring size; the watchdog stall dump and
        ``aggregates()`` see at most this many trailing records.
    :param memory_every: attach host RSS + device HBM stats to every
        N-th record (0 disables the memory fields entirely).
    :param filename: JSONL file name inside ``run_dir``.

    Thread-safe: the serving path records from worker threads.
    """

    def __init__(self, run_dir=None, capacity: int = 512,
                 memory_every: int = 16,
                 filename: str = "telemetry.jsonl"):
        self.capacity = int(capacity)
        self.memory_every = int(memory_every)
        self.ring: "collections.deque" = collections.deque(
            maxlen=self.capacity
        )
        # _lock guards ONLY the ring + counter (never held across I/O or
        # device probes): the watchdog's stall dump reads the ring from
        # its monitor thread, and a wedged file write or memory_stats()
        # call — exactly the stalls it diagnoses — must not deadlock it.
        # _io_lock serializes the JSONL file.
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self._n = 0
        self._file = None
        self.path = None
        if run_dir is not None:
            self.path = Path(run_dir) / filename
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._file = open(self.path, "a", buffering=1)  # line-buffered
            _register_for_atexit(self)
        _install_compile_listener()
        _install_gc_callback()

    # -- write ---------------------------------------------------------------

    def record(self, step: int, **fields) -> dict:
        """Append one step record; returns the full record as written.

        Non-finite floats are nulled (strict-JSON consumers choke on
        NaN/Infinity); None-valued fields are dropped."""
        rec = {"v": SCHEMA_VERSION, "step": int(step),
               "t": round(time.time(), 3)}
        for k, v in fields.items():
            if v is None:
                continue
            if (not isinstance(v, (bool, int, float, str, bytes))
                    and hasattr(v, "item")):
                # numpy/jax scalars: unwrap to builtins so the
                # non-finite nulling below sees them and json.dumps
                # never chokes on a caller's un-converted scalar
                try:
                    v = v.item()
                except Exception:
                    pass
            if isinstance(v, float) and (v != v or v in (float("inf"),
                                                         float("-inf"))):
                v = None
            rec[k] = v
        compile_events = drain_compile_events()
        if compile_events:
            # EXTEND a caller-provided list rather than replace it: a
            # deferred record (trainer sync-free logging) drains at
            # enqueue time so its own compile rides under its own step,
            # and anything arriving before the flush still lands here
            rec["compile_events"] = (
                list(rec.get("compile_events") or []) + compile_events
            )
        with self._lock:
            self._n += 1
            attach_memory = (
                self.memory_every
                and (self._n - 1) % self.memory_every == 0
            )
        if attach_memory:  # probes run OUTSIDE the ring lock (see init)
            rss = host_rss_bytes()
            if rss:
                rec["host_rss_mb"] = round(rss / 2**20, 1)
            devices = device_memory_stats()
            if devices:
                rec["devices"] = devices
        with self._lock:
            self.ring.append(rec)
        with self._io_lock:
            if self._file is not None:
                try:
                    # default=repr: one exotic caller field must not
                    # void the line (same policy as SpanRecorder.dump)
                    self._file.write(json.dumps(rec, default=repr) + "\n")
                except (OSError, ValueError, TypeError):
                    pass  # a full disk must never kill the step loop
        return rec

    # -- read ----------------------------------------------------------------

    def last(self, n: Optional[int] = None) -> list:
        """The trailing ``n`` records (all buffered when None)."""
        with self._lock:
            records = list(self.ring)
        return records if n is None else records[-int(n):]

    def aggregates(self) -> dict:
        """Throughput over the buffered window, computed from the
        records themselves: steps/s from
        summed ``wall_ms``, tokens/s and examples/s from the summed
        ``tokens``/``examples`` fields over the same wall time."""
        records = self.last()
        timed = [r for r in records if r.get("wall_ms")]
        if not timed:
            return {"steps": len(records)}
        wall_s = sum(r["wall_ms"] for r in timed) / 1e3
        out = {
            "steps": len(timed),
            "wall_s": round(wall_s, 3),
            "steps_per_sec": round(len(timed) / wall_s, 4),
        }
        tokens = sum(r.get("tokens", 0) for r in timed)
        if tokens:
            out["tokens_per_sec"] = round(tokens / wall_s, 1)
        examples = sum(r.get("examples", 0) for r in timed)
        if examples:
            out["examples_per_sec"] = round(examples / wall_s, 1)
        waits = [r["data_wait_ms"] for r in timed
                 if r.get("data_wait_ms") is not None]
        if waits:
            out["data_wait_ms_mean"] = round(sum(waits) / len(waits), 3)
        losses = [r["loss"] for r in timed if r.get("loss") is not None]
        if losses:
            out["last_loss"] = losses[-1]
        return out

    # -- lifecycle -----------------------------------------------------------

    def flush(self) -> None:
        with self._io_lock:
            if self._file is not None:
                try:
                    self._file.flush()
                    os.fsync(self._file.fileno())
                except (OSError, ValueError):
                    pass

    def close(self) -> None:
        with self._io_lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_jsonl(path) -> list:
    """Load a telemetry JSONL file back into a list of records —
    the round-trip consumers (tests, dashboards) use."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records
