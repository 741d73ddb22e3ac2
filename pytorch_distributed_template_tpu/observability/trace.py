"""Lightweight span tracing: ``with span("data/next_batch"): ...``.

Host-side structured timing for the paths ``jax.profiler`` cannot see
(it traces device programs; the question "was the step slow because of
data wait, checkpoint flush, or the dispatch itself?" is a HOST
timeline question). Spans nest, survive exceptions, cost two
``perf_counter`` calls plus a deque append, and record into a bounded
ring as Chrome trace-event ``"X"`` (complete) events — ``dump()``
writes a file that chrome://tracing and Perfetto load directly.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
and attrs: while a profiler capture runs (``TraceCapture``, SIGUSR2),
the span lands on the calling thread's line of the ``.xplane.pb``, on
the device events' clock, so an idle gap of the device can be laid
against what the host was inside. Outside a capture the annotation is
a flag test. The ring keeps its own ``perf_counter`` origin.

Consumers beyond the viewers:

- the watchdog (utils/watchdog.py) snapshots ``active_spans()`` when a
  step stalls, so the dump says WHICH call never returned ("stuck 214 s
  inside checkpoint/save") next to the faulthandler stacks;
- the trainer reads its own set-up's phases back (``since()``) into the
  first flight record's ``setup``, and names the other threads' open
  spans on a stalled iteration's ``stall``;
- tests assert nesting and exception safety on the recorded events.

The module-level ``span()`` uses one process-wide recorder
(``get_recorder()``); subsystems that want isolation construct their
own ``SpanRecorder`` and use its ``.span()`` method.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Optional


class SpanRecorder:
    """Bounded ring of finished spans + registry of open ones.

    Chrome trace-event fields per finished span: ``name``, ``ph: "X"``,
    ``ts``/``dur`` (microseconds, one process-wide monotonic origin),
    ``pid``/``tid``, and ``args`` (user attrs; ``error: true`` when the
    body raised).
    """

    def __init__(self, capacity: int = 8192):
        self.capacity = int(capacity)
        self.events: "collections.deque" = collections.deque(
            maxlen=self.capacity
        )
        self._lock = threading.Lock()
        # open spans per thread: {tid: [ {name, t0, args}, ... ]}
        self._open: dict = {}
        self._t0 = time.perf_counter()  # trace time origin

    # -- the core API --------------------------------------------------------

    @contextmanager
    def span(self, name: str, **attrs):
        # imported here: the module stays importable where jax is not
        # (the fleet router's process reaches it through the package)
        from jax.profiler import TraceAnnotation

        tid = threading.get_ident()
        t0 = time.perf_counter()
        frame = {"name": name, "t0": t0, "args": attrs}
        with self._lock:
            self._open.setdefault(tid, []).append(frame)
        try:
            with TraceAnnotation(name, **attrs):
                yield frame
        except BaseException:
            frame["args"] = {**frame["args"], "error": True}
            raise
        finally:
            t1 = time.perf_counter()
            with self._lock:
                stack = self._open.get(tid)
                if stack and stack[-1] is frame:
                    stack.pop()
                    if not stack:
                        del self._open[tid]
                event = {
                    "name": name,
                    "ph": "X",
                    "ts": round((t0 - self._t0) * 1e6, 1),
                    "dur": round((t1 - t0) * 1e6, 1),
                    "pid": os.getpid(),
                    "tid": tid,
                }
                if frame["args"]:
                    event["args"] = dict(frame["args"])
                self.events.append(event)

    # -- introspection -------------------------------------------------------

    def active_spans(self) -> list:
        """Currently-open spans across all threads, outermost first —
        the watchdog's 'what is the process stuck inside' snapshot."""
        now = time.perf_counter()
        out = []
        with self._lock:
            for tid, stack in self._open.items():
                for depth, frame in enumerate(stack):
                    out.append({
                        "tid": tid,
                        "depth": depth,
                        "name": frame["name"],
                        "elapsed_ms": round((now - frame["t0"]) * 1e3, 3),
                        **({"args": dict(frame["args"])}
                           if frame["args"] else {}),
                    })
        return out

    def snapshot(self) -> list:
        with self._lock:
            return list(self.events)

    def since(self, t0: float) -> list:
        """Finished events that began at or after ``t0``, a
        ``perf_counter`` reading (a span's ``frame["t0"]``): what a
        trainer reads for its own set-up's phases, where the ring holds
        other trainers' too. Their ``ts`` is on the ring's clock;
        ``(e["ts"] - first["ts"]) / 1e6`` is seconds after the first."""
        ts = round((t0 - self._t0) * 1e6, 1)
        with self._lock:
            return [e for e in self.events if e["ts"] >= ts]

    # -- output --------------------------------------------------------------

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (load in chrome://tracing
        or Perfetto)."""
        return {"traceEvents": self.snapshot(),
                "displayTimeUnit": "ms"}

    def dump(self, path) -> Optional[Path]:
        """Write the Chrome trace file; returns the path (None when
        nothing was recorded)."""
        events = self.to_chrome()
        if not events["traceEvents"]:
            return None
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        # default=repr: span attrs are caller-arbitrary, and a single
        # non-JSON attr must not void the whole trace file
        path.write_text(json.dumps(events, default=repr))
        return path

    def clear(self) -> None:
        with self._lock:
            self.events.clear()


_default = SpanRecorder()
_said: set = set()


def get_recorder() -> SpanRecorder:
    """The process-wide recorder behind the module-level ``span()``."""
    return _default


def span(name: str, **attrs):
    """``with span("checkpoint/save"): ...`` on the default recorder."""
    return _default.span(name, **attrs)


def say_once(logger, name: str, record: dict, text: str, *args) -> None:
    """A choice the program makes from shapes, told once a process and
    distinct choice: one zero-length span ``name`` that carries
    ``record``, and one ``INFO`` line, ``text`` filled from ``args`` or,
    without any, from ``record`` by name. Read by no metric; the first
    thing to read when the metric it moves does."""
    key = (name, tuple(record.items()))
    if key in _said:
        return
    _said.add(key)
    with span(name, **record):
        pass
    logger.info(text, *(args or (record,)))
