"""Background AOT warmup of the compiled steps (warm-path leg 2).

The first invocation of a jitted step traces + XLA-compiles before
executing; on big models that is minutes of dead chip time at the start
of every run. Dataset/loader startup (corpus read, tokenizer training,
shard mmap) runs on the host at the same moment and does not need the
compiler — so this module overlaps them: a background thread
``lower().compile()``s the train/eval steps from *abstract* batches
(``jax.ShapeDtypeStruct`` built from the loader's array specs, never a
real batch) while the trainer finishes its init, and the compiled
executables are installed before step 1.

Two contracts make this safe:

- the warmup CALLS the compiled executable thereafter (via
  ``engine.steps.instrument_step``) instead of hoping the AOT compile
  seeded the dispatch-path jit cache — the same reasoning as the
  serving engine's chunk-ladder warmup (engine/continuous.py), which
  found AOT-then-jit "probably warms" is not a guarantee;
- every failure path (lowering error, backend quirk, unexpected
  dtype) degrades to the lazy jit path with one warning — warmup is an
  optimization, never a dependency. A shape that later diverges from
  the abstract spec raises from the compiled executable; the trainer's
  loaders pad to static shapes, so that indicates a real bug upstream,
  not a warmup limitation.

Composes with the persistent compilation cache (utils/compile_cache):
warm runs satisfy the background compile from disk in seconds, so the
thread finishes long before the first batch is assembled.
"""
from __future__ import annotations

import logging
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)


def abstract_batch(loader, sharding, transform=None,
                   batch_size: Optional[int] = None) -> dict:
    """``jax.ShapeDtypeStruct`` pytree matching what
    ``data.loader.prefetch_to_device`` will feed the step: one leaf per
    loader array at the padded static batch size, plus the ``mask``
    row-validity vector, each carrying the batch ``sharding`` so AOT
    lowering sees exactly the layouts the real transfer produces.

    ``transform`` (the loader's ``device_transform``) is traced through
    ``jax.eval_shape`` so dtype changes (uint8 -> normalized float32)
    land in the abstract batch too. On multi-host meshes the global
    batch dim is ``process_count`` host shards of the local batch —
    the ``make_array_from_process_local_data`` assembly contract.
    """
    import jax

    b = int(batch_size if batch_size is not None else loader.batch_size)
    b *= jax.process_count()
    sds = {
        k: jax.ShapeDtypeStruct((b,) + tuple(v.shape[1:]), v.dtype)
        for k, v in loader.arrays.items()
    }
    norm = getattr(loader, "normalize", None)
    if norm and not getattr(loader, "_norm_on_device", False):
        # HOST-side gather-normalization (loader.py gather_normalize):
        # the stored array stays uint8 but every batch leaves the host
        # float32 — the spec must describe the batch, not the storage
        key = norm.get("key", "image")
        if key in sds:
            sds[key] = jax.ShapeDtypeStruct(sds[key].shape, np.float32)
    sds["mask"] = jax.ShapeDtypeStruct((b,), np.dtype(bool))
    if transform is not None:
        sds = jax.eval_shape(transform, sds)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=sharding),
        sds,
    )


class StepWarmup:
    """Compile registered jitted steps on one background thread.

    Usage (the trainer's init sequence)::

        warmup = StepWarmup()
        warmup.add("train_step", jitted_train, state, abstract_batch)
        warmup.add("eval_step", jitted_eval, state, abstract_eval_batch)
        warmup.start()
        ...                      # loader/dataset startup overlaps here
        compiled = warmup.result("train_step")   # None on failure

    ``add`` arguments may mix concrete arrays (the real state — its
    avals and shardings are exactly what the first call passes) with
    ``ShapeDtypeStruct``s; nothing is executed, only
    ``trace(*args).lower().compile()``, each stage under its span
    ``warmup/<name>/trace|lower|compile`` on this thread. Jobs compile
    in registration order on one thread (the compiler parallelizes
    internally; a second host thread would just contend). ``result``
    blocks until that job
    settles — by the first step the compile is normally long done, and
    when it is not, waiting on the in-flight compile is strictly no
    worse than starting the same compile lazily.
    """

    def __init__(self):
        self._jobs: list = []        # (name, fn, args)
        self._done: dict = {}        # name -> threading.Event
        self._compiled: dict = {}    # name -> compiled executable
        self._thread: Optional[threading.Thread] = None

    def add(self, name: str, jitted_fn, *args) -> None:
        if self._thread is not None:
            raise RuntimeError("warmup thread already started")
        self._jobs.append((name, jitted_fn, args))
        self._done[name] = threading.Event()

    def start(self) -> "StepWarmup":
        if self._thread is None and self._jobs:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="aot-warmup")
            self._thread.start()
        return self

    def _run(self) -> None:
        from ..observability.trace import span

        for name, fn, args in self._jobs:
            try:
                # fn.lower(*args).compile() by its stages, one span
                # each, since each has its own remedy: Python tracing,
                # lowering to MLIR, the backend's compile or cache read
                with span(f"warmup/{name}/trace"):
                    traced = fn.trace(*args)
                with span(f"warmup/{name}/lower"):
                    lowered = traced.lower()
                with span(f"warmup/{name}/compile"):
                    self._compiled[name] = lowered.compile()
            except Exception:  # noqa: BLE001 — degrade to lazy compile
                logger.warning(
                    "AOT warmup of %s failed; falling back to lazy "
                    "compile on first call", name, exc_info=True,
                )
            finally:
                self._done[name].set()
        self._jobs = []  # release the arg references (state, specs)

    def result(self, name: str, timeout: Optional[float] = None):
        """The compiled executable for ``name``, or None (unknown name,
        compile failed, or ``timeout`` expired while still compiling)."""
        ev = self._done.get(name)
        if ev is None:
            return None
        ev.wait(timeout)
        return self._compiled.get(name)
