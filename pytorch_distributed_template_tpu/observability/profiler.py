"""Profiling: throughput, MFU, and on-demand XLA trace capture.

The reference's only performance instrumentation is a ``steps_per_sec``
TensorBoard scalar derived from wall-clock deltas between logging calls
(/root/reference/logger/visualization.py:40-48). This module supplies the
TPU-native tier promised in SURVEY.md §5 "Tracing / profiling":

- ``ThroughputMeter``: honest steps/sec + examples/sec over timing windows
  (the reference's number was really *logging-calls*/sec — kept for TB
  parity in ``TensorboardWriter.set_step``, while this meter feeds the real
  values).
- ``compiled_flops``: cost analysis of the *compiled* XLA executable — the
  exact FLOPs the hardware will run (post-fusion), not an analytic estimate.
- ``mfu``: model FLOPs utilization against the chip's peak, with a device
  table for TPU generations (config key
  ``trainer.profiler.peak_flops_per_device`` for a device it lacks).
- ``TraceCapture``: a step-windowed ``jax.profiler`` trace (view in
  TensorBoard's profile plugin) — start/stop driven by the trainer's step
  counter so the capture covers steady-state steps, not compilation.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Optional

import jax

# Peak dense bf16/fp16 FLOPs per *chip*, by device_kind substring (lowercase,
# first match wins; order matters: "v5 lite" before "v5"). Public numbers
# from the TPU generation announcements.
PEAK_FLOPS_TABLE = (
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4 lite", 137e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_device(device=None) -> Optional[float]:
    """Peak FLOPs/s for one device, or None when unknown (e.g. CPU)."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_FLOPS_TABLE:
        if key in kind:
            return val
    return None


def executable_flops(compiled) -> Optional[float]:
    """FLOPs of one invocation of an already-compiled executable, from
    XLA's cost analysis (post-fusion). Returns None when the backend
    doesn't report."""
    try:
        flops = compiled.cost_analysis().get("flops")
        return float(flops) if flops else None
    except Exception:
        return None


def compiled_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of one invocation, from XLA's cost analysis of the compiled
    executable (post-fusion). Returns None when the backend doesn't report.

    Note: this runs an AOT lower+compile of ``jitted_fn`` for the given
    shapes; call it once at startup (compilation is cached per shape on most
    backends, but do not put this in the hot loop).
    """
    try:
        return executable_flops(jitted_fn.lower(*args, **kwargs).compile())
    except Exception:
        return None


def mfu(flops_per_step: Optional[float], steps_per_sec: float,
        peak_per_device: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization in [0, 1]; None when peak/flops unknown.

    ``flops_per_step`` is the *per-device* figure: under SPMD partitioning,
    ``cost_analysis`` on the compiled executable reports the partitioned
    per-device module (on one device that equals the whole program), so it
    is compared against a single device's peak.
    """
    if not flops_per_step or not steps_per_sec:
        return None
    if peak_per_device is None:
        peak_per_device = peak_flops_per_device()
    if peak_per_device is None:
        return None
    return (flops_per_step * steps_per_sec) / peak_per_device


class ThroughputMeter:
    """Windowed steps/sec + examples/sec.

    ``update(n_examples)`` once per step; ``rate()`` returns the rates since
    the last ``rate()``/``reset()`` call and opens a new window. The first
    window of an epoch includes compilation unless ``reset`` is called after
    the first step (the trainer does).
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()
        self._steps = 0
        self._examples = 0

    def update(self, n_examples: int = 0) -> None:
        self._steps += 1
        self._examples += int(n_examples)

    def rate(self) -> dict:
        dt = max(time.perf_counter() - self._t0, 1e-9)
        out = {
            "steps_per_sec": self._steps / dt,
            "examples_per_sec": self._examples / dt,
        }
        self.reset()
        return out


class TraceCapture:
    """Step-windowed ``jax.profiler`` trace into ``<log_dir>/profile``.

    :param log_dir: run log dir; traces land in its ``profile/`` subdir.
    :param start_step: first step included in the capture (global step).
    :param num_steps: how many steps to capture (0: nothing scheduled —
        but ``request()`` can still arm a capture at runtime).

    Call ``before_step(step)`` / ``after_step(step)`` around each train
    step; idempotent and a no-op while no window is armed.

    ``request(n)`` arms an ON-DEMAND n-step capture starting at the next
    step — signal-handler-safe (it only assigns one attribute), which is
    how ``train.py`` wires it to SIGUSR2: profile a live run exactly
    when it misbehaves, no restart, no config edit. Each completed
    capture bumps the process-wide ``profile_captures_total`` counter
    (observability/health) and, when a recorder is attached, lands an
    ``event: "profile_capture"`` record on the telemetry timeline.
    """

    def __init__(self, log_dir, start_step: int = 10, num_steps: int = 0):
        self.dir = str(Path(log_dir) / "profile")
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self._active = False
        self._done = self.num_steps <= 0
        self._requested: Optional[int] = None
        self.captures = 0
        self.recorder = None
        self._spent_ms: Optional[float] = None  # see take_ms()

    def attach_recorder(self, recorder) -> None:
        """Optional FlightRecorder that capture completions get noted on."""
        self.recorder = recorder

    def request(self, num_steps: int = 5) -> None:
        """Arm an on-demand capture of ``num_steps`` steps starting at
        the next ``before_step``. Safe from signal handlers / other
        threads (single attribute write); ignored while a capture is
        already in flight — a second SIGUSR2 during a slow capture must
        not latch a surprise extra trace for after it closes."""
        if self._active:
            return
        self._requested = max(int(num_steps), 1)

    def take_ms(self) -> Optional[float]:
        """Milliseconds spent starting or stopping a capture (the stop
        waits for the captured steps and writes the trace: seconds)
        since the last call, or None where neither happened: the
        trainer's ``profile_ms``, on the iterations that hold one."""
        spent, self._spent_ms = self._spent_ms, None
        return spent

    def _timed(self, call, *args) -> None:
        t0 = time.perf_counter()
        call(*args)
        self._spent_ms = ((self._spent_ms or 0.0)
                          + (time.perf_counter() - t0) * 1e3)

    def before_step(self, step: int) -> None:
        if self._active:
            return
        if self._requested is not None:
            # runtime trigger: re-arm regardless of the config-scheduled
            # window having been consumed
            self.num_steps = self._requested
            self._requested = None
            self._done = False
            self.start_step = step
        if not self._done and step >= self.start_step:
            Path(self.dir).mkdir(parents=True, exist_ok=True)
            self._timed(jax.profiler.start_trace, self.dir)
            self._active = True
            self._until = step + self.num_steps

    def after_step(self, step: int, sync=None) -> None:
        """``sync``: step outputs to ``block_until_ready`` before stopping —
        steps are dispatched asynchronously, so without it the trace would
        close while the captured steps still run on device."""
        if self._active and step + 1 >= self._until:
            if sync is not None:
                self._timed(jax.block_until_ready, sync)
            self._timed(jax.profiler.stop_trace)
            self._active = False
            self._done = True
            self._note_capture(step)

    def _note_capture(self, step: int) -> None:
        self.captures += 1
        try:
            from .health import bump_counter

            bump_counter("profile_captures_total")
        except Exception:  # noqa: BLE001
            pass
        if self.recorder is not None:
            try:
                self.recorder.record(
                    step, event="profile_capture",
                    profile_dir=self.dir, profile_steps=self.num_steps,
                )
            except Exception:  # noqa: BLE001
                pass

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            self._note_capture(self.start_step + self.num_steps)


def install_sigusr2(trace: TraceCapture, default_steps: int = 5) -> bool:
    """SIGUSR2 -> ``trace.request(n)``: on-demand profiling of a live
    training run (``kill -USR2 <pid>``). ``PDT_PROFILE_STEPS`` overrides
    the window length. Returns False on platforms without SIGUSR2 or
    when not called from the main thread (signal module restriction)."""
    import signal

    if not hasattr(signal, "SIGUSR2"):
        return False

    def _handler(signum, frame):
        try:
            n = int(os.environ.get("PDT_PROFILE_STEPS", default_steps))
        except ValueError:
            n = default_steps
        trace.request(n)

    try:
        signal.signal(signal.SIGUSR2, _handler)
        return True
    except ValueError:  # not the main thread
        return False


class OnDemandProfiler:
    """Progress-windowed on-demand capture for step-less processes
    (serve.py's ``POST /profile?steps=N``).

    The serving schedulers have no global step counter, but they DO have
    monotonic progress counters (continuous engine: ``chunks``; static:
    ``batches``/``requests``). ``capture()`` starts a ``jax.profiler``
    trace, waits until ``progress_fn`` has advanced by ``steps`` (or
    ``timeout_s`` passes — an idle server must not pin a request thread
    forever), stops, and reports what it saw. One capture at a time:
    concurrent callers get ``busy``.
    """

    def __init__(self, out_dir):
        import threading

        self.dir = str(Path(out_dir) / "profile")
        self._lock = threading.Lock()
        self.captures = 0

    def capture(self, steps: int = 0, progress_fn=None,
                timeout_s: float = 30.0, poll_s: float = 0.05) -> dict:
        if not self._lock.acquire(blocking=False):
            return {"busy": True,
                    "error": "a profile capture is already running"}
        try:
            Path(self.dir).mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            t0 = time.monotonic()
            base = progress_fn() if (progress_fn and steps > 0) else 0
            seen, timed_out = 0, False
            while progress_fn is not None and steps > 0:
                seen = progress_fn() - base
                if seen >= steps:
                    break
                if time.monotonic() - t0 > timeout_s:
                    timed_out = True
                    break
                time.sleep(poll_s)
            jax.profiler.stop_trace()
            self.captures += 1
            try:
                from .health import bump_counter

                bump_counter("profile_captures_total")
            except Exception:  # noqa: BLE001
                pass
            return {
                "profile_dir": self.dir,
                "steps_requested": int(steps),
                "steps_observed": int(seen),
                "duration_s": round(time.monotonic() - t0, 3),
                "timed_out": timed_out,
                "captures_total": self.captures,
            }
        except Exception as e:  # noqa: BLE001 — surface, don't kill serve
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
            return {"error": f"{type(e).__name__}: {e}"}
        finally:
            self._lock.release()
