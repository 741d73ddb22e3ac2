"""Training loops: epoch policy up top, one compiled SPMD step underneath.

Mirrors the reference's BaseTrainer/Trainer split
(/root/reference/base/base_trainer.py + trainer/trainer.py): the base class
owns the epoch loop, metric monitoring, best-model tracking, early stopping,
and checkpoint policy; the concrete Trainer owns the per-epoch batch loop.

Key structural translation (SURVEY.md §3.1 hot loop -> jit):
- reference per-batch Python (H2D, forward, loss, dist.reduce, backward,
  DDP allreduce, step) -> ONE jitted ``train_step`` consuming pre-sharded
  prefetched batches, with the state donated (no copy per step);
- validation gathers nothing: metric sufficient statistics are psum'd
  in-graph and every host ends the epoch with identical global values.
  Because of that, monitor/early-stop decisions are *deterministically
  identical* on every host — the reference's pickle ``all_gather`` consensus
  (base_trainer.py:101-107) degenerates to plain local control flow here;
  rank gating remains only for I/O (logging, TB, checkpoint metadata);
- the reference's per-epoch ``lr_scheduler.step()`` is a pure function of
  the step counter compiled into the optimizer (engine/optim.py).
"""
from __future__ import annotations

import hashlib
import math
import os
import time
from abc import abstractmethod
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..data.loader import host_prefetch, prefetch_to_device
from ..models.base import describe, inject_mesh
from ..observability import FlightRecorder, MetricTracker, TensorboardWriter
from ..observability.crosshost import CrossHostAggregator
from ..observability.health import (
    HealthMonitor, health_counters, health_layout, health_metric_keys,
)
from ..observability.telemetry import (
    IterationAccount, drain_compile_events,
)
from ..observability.trace import get_recorder as get_span_recorder
from ..observability.trace import span
from ..ops.augment import build_augment
from ..observability.profiler import (
    ThroughputMeter, TraceCapture, compiled_flops, mfu,
)
from ..parallel import (
    batch_sharding, dist, mesh_from_config, train_step_compile_options,
)
from ..resilience import faults
from ..utils import preemption
from ..utils.debug import configure_debug
from ..utils.util import maybe_tqdm
from ..utils.watchdog import StepWatchdog
from .optim import build_optimizer
from .state import create_sharded_train_state
from .steps import (
    COUNTER_PREFIX, finalize_metrics, instrument_step, make_eval_step,
    make_train_step,
)


def _endless_reshuffling(loader):
    """Endless loader for iteration-based training that reshuffles on every
    full pass (the reference's ``inf_loop`` relies on torch DataLoader
    reshuffling per re-iteration, utils/util.py:24-27; ours must advance
    the epoch counter explicitly or every pass replays one permutation)."""
    pass_idx = 0
    while True:
        if hasattr(loader, "set_epoch"):
            loader.set_epoch(pass_idx)
        yield from loader
        pass_idx += 1


class BaseTrainer:
    """Epoch-policy skeleton (reference base/base_trainer.py:10-107)."""

    def __init__(self, config):
        self.config = config
        cfg_trainer = config["trainer"]
        self.logger = config.get_logger(
            "trainer", cfg_trainer.get("verbosity", 2)
        )
        self.epochs = cfg_trainer["epochs"]
        self.save_period = cfg_trainer.get("save_period", 1)
        # mid-epoch safety net for long epochs (0 = off): every N batches
        # an async save lands in the alternating checkpoint-interval-a/b
        # slots (manager.save_interval), so a crash loses at most N steps.
        # Deterministic host-side condition -> every host saves together
        # (orbax saves are collective). Same partial-epoch resume semantics
        # as preemption: resume continues at the next epoch.
        self.save_interval_steps = int(
            cfg_trainer.get("save_interval_steps", 0)
        )
        self.monitor = cfg_trainer.get("monitor", "off")

        if self.monitor == "off":
            self.mnt_mode = "off"
            self.mnt_best = 0
        else:
            self.mnt_mode, self.mnt_metric = self.monitor.split()
            assert self.mnt_mode in ("min", "max")
            self.mnt_best = math.inf if self.mnt_mode == "min" else -math.inf
            self.early_stop = cfg_trainer.get("early_stop", math.inf)
            # None (e.g. ``--set "trainer;early_stop" null``) or <=0 both
            # mean "never stop early".
            if self.early_stop is None or self.early_stop <= 0:
                self.early_stop = math.inf

        self.start_epoch = 1
        # (epoch, next_batch) cursor maintained by the batch loop —
        # what the data_state sidecar and the emergency save record
        self._cursor = None
        self._resume_next_batch = 0
        self.checkpoint_dir = config.save_dir
        self.ckpt_manager = CheckpointManager(self.checkpoint_dir)
        self.writer = TensorboardWriter(
            config.log_dir, self.logger, cfg_trainer.get("tensorboard", False)
        )

    @abstractmethod
    def _train_epoch(self, epoch: int) -> dict:
        raise NotImplementedError

    def train(self) -> dict:
        """Full training loop (reference base_trainer.py:60-107).

        Monitoring runs identically on every host (epoch metrics are global
        device reductions, so all hosts agree bit-for-bit); only I/O is
        gated on the main process. Early stop therefore needs no cross-host
        consensus exchange.
        """
        preemption.install()
        not_improved_count = 0
        log: dict = {}
        try:
            for epoch in range(self.start_epoch, self.epochs + 1):
                result = self._train_epoch(epoch)

                log = {"epoch": epoch}
                log.update(result)
                if dist.is_main_process():
                    for key, value in log.items():
                        self.logger.info("    %-15s: %s", str(key), value)

                best = False
                if self.mnt_mode != "off":
                    try:
                        improved = (
                            self.mnt_mode == "min"
                            and log[self.mnt_metric] <= self.mnt_best
                        ) or (
                            self.mnt_mode == "max"
                            and log[self.mnt_metric] >= self.mnt_best
                        )
                    except KeyError:
                        if dist.is_main_process():
                            self.logger.warning(
                                "Warning: Metric '%s' is not found. Model "
                                "performance monitoring is disabled.",
                                self.mnt_metric,
                            )
                        self.mnt_mode = "off"
                        improved = False

                    if improved:
                        self.mnt_best = log[self.mnt_metric]
                        not_improved_count = 0
                        best = True
                    else:
                        not_improved_count += 1

                if preemption.sync_requested():
                    # any host got SIGTERM: checkpoint NOW (regardless of
                    # save_period) and stop everywhere together — resume
                    # loses at most the in-flight epoch (utils/preemption.py)
                    if dist.is_main_process():
                        self.logger.warning(
                            "Preemption signal received; saving checkpoint "
                            "at epoch %d and stopping.", epoch,
                        )
                    self._save_checkpoint(epoch, save_best=best)
                    break

                if epoch % self.save_period == 0:
                    self._save_checkpoint(epoch, save_best=best)

                if (self.mnt_mode != "off"
                        and not_improved_count > self.early_stop):
                    if dist.is_main_process():
                        self.logger.info(
                            "Validation performance didn't improve for %s "
                            "epochs. Training stops.", self.early_stop,
                        )
                    break
        except Exception as exc:
            # unhandled-exception emergency checkpoint (resilience
            # subsystem): land the live state + data_state before the
            # process dies, so the supervisor's relaunch resumes at the
            # exact next batch instead of the last periodic save. The
            # original exception always propagates.
            self._emergency_save(exc)
            raise
        finally:
            # stop the watchdog FIRST: no steps run past this point, and
            # the async checkpoint flush below can legitimately take
            # longer than the stall threshold
            watchdog = getattr(self, "watchdog", None)
            if watchdog is not None:
                watchdog.stop()
            if watchdog is not None:
                # the final flush can legitimately outlast the
                # supervisor's hang timeout; keep the external
                # heartbeat alive so a healthy finishing run is not
                # SIGKILLed mid-checkpoint-write
                with watchdog.heartbeat_keepalive():
                    self.ckpt_manager.wait()
            else:
                self.ckpt_manager.wait()
            trace = getattr(self, "trace", None)
            if trace is not None:
                trace.close()  # flush a still-open profiler window
            recorder = getattr(self, "recorder", None)
            if recorder is not None:
                recorder.close()
            if dist.is_main_process():
                # host-span timeline as a Chrome trace-event file
                # (chrome://tracing / Perfetto); complements the XLA
                # profiler's device capture in log_dir/profile
                try:
                    get_span_recorder().dump(
                        self.config.log_dir / "trace.json"
                    )
                except Exception:  # teardown diagnostics must not
                    self.logger.warning("could not write trace.json",
                                        exc_info=True)  # crash the run
            self._write_summary(log)
        return log

    def _write_summary(self, log: dict) -> None:
        """Machine-readable run outcome: ``summary.json`` in the run dir
        (final epoch's metrics, the monitored best, where it stopped).
        The reference's outcome lives only in info.log text; tooling around
        experiments (sweeps, dashboards, the relaunch loop) wants JSON."""
        if not dist.is_main_process() or not log:
            return
        try:
            import json

            summary = {
                **{k: (v if isinstance(v, int) else
                       float(v) if isinstance(v, float) else v)
                   for k, v in log.items()},
                "monitor": f"{self.mnt_mode} {self.mnt_metric}"
                           if self.mnt_mode != "off" else "off",
                # +/-inf means "no epoch ever improved" (e.g. NaN metrics);
                # json.dumps would emit non-standard Infinity, so map to None.
                "monitor_best": (
                    float(self.mnt_best)
                    if self.mnt_mode != "off" and math.isfinite(self.mnt_best)
                    else None
                ),
                "run_dir": str(self.config.save_dir),
                "device": dist.device_summary(),
            }
            (self.config.save_dir / "summary.json").write_text(
                json.dumps(summary, indent=2)
            )
        except Exception:  # never let bookkeeping kill a finished run
            self.logger.warning("could not write summary.json",
                                exc_info=True)

    def _save_checkpoint(self, epoch: int, save_best: bool = False) -> None:
        raise NotImplementedError

    # -- resilience: emergency save + data_state sidecar --------------------

    def _data_state_snapshot(self) -> Optional[dict]:
        """The step-accurate-resume sidecar for the state being saved:
        where the NEXT batch after this checkpoint lives (epoch +
        batch ordinal, normalized past epoch edges), plus the sampler
        cursor and an RNG fingerprint for forensics. None when the
        trainer has no cursor yet (nothing ran)."""
        if self._cursor is None:
            return None
        epoch, next_batch = self._cursor
        len_epoch = int(getattr(self, "len_epoch", 0) or 0)
        if len_epoch and next_batch >= len_epoch:
            epoch, next_batch = epoch + 1, 0
        ds = {
            "epoch": int(epoch),
            "next_batch": int(next_batch),
            "len_epoch": len_epoch,
        }
        state = getattr(self, "state", None)
        if state is not None:
            try:
                import jax as _jax

                ds["global_step"] = int(_jax.device_get(state.step))
                key_bytes = np.asarray(
                    _jax.device_get(_jax.random.key_data(state.rng))
                ).tobytes()
                ds["rng_fingerprint"] = hashlib.sha256(
                    key_bytes).hexdigest()[:12]
            except Exception:  # sidecar forensics must not block a save
                pass
        loader = getattr(self, "train_loader", None)
        if loader is not None:
            ds["batch_size"] = int(getattr(loader, "batch_size", 0))
            sampler = getattr(loader, "sampler", None)
            if sampler is not None and hasattr(sampler, "state"):
                ds["sampler"] = sampler.state()
            else:
                ds["shuffle"] = bool(getattr(loader, "shuffle", False))
                ds["data_seed"] = int(getattr(loader, "seed", 0))
        return ds

    def _emergency_save(self, exc: Exception) -> None:
        """Best-effort checkpoint on the unhandled-exception path.

        Skipped when (a) disabled (``trainer.emergency_checkpoint:
        false``), (b) the exception IS a checkpoint-write fault
        (re-entering the failing checkpointer would double-fault), or
        (c) there is no state yet. Never raises — the original
        exception is the story, this is just the save of what survives
        it."""
        if not bool(self.config["trainer"].get("emergency_checkpoint",
                                               True)):
            return
        if getattr(exc, "is_checkpoint_fault", False):
            self.logger.warning(
                "Emergency checkpoint SKIPPED: the failure is the "
                "checkpoint path itself (%s).", exc,
            )
            return
        state = getattr(self, "state", None)
        model = getattr(self, "model", None)
        if state is None or self._cursor is None:
            return
        try:
            self.ckpt_manager.save_emergency(
                epoch=self._cursor[0],
                state=state,
                arch=type(model).__name__ if model is not None else "?",
                config=dict(self.config.config),
                monitor_best=(
                    self.mnt_best
                    if isinstance(self.mnt_best, (int, float)) else 0.0
                ),
                data_state=self._data_state_snapshot(),
            )
            self.logger.warning(
                "Emergency checkpoint saved after %s: %s",
                type(exc).__name__, exc,
            )
        except Exception:  # noqa: BLE001 — never mask the original error
            self.logger.warning(
                "Emergency checkpoint failed (original error propagates)",
                exc_info=True,
            )


class Trainer(BaseTrainer):
    """Concrete trainer (reference trainer/trainer.py:11-123), jit-compiled.

    :param model: a flax module from the MODELS registry.
    :param criterion: per-example loss ``(output, target) -> [B]``.
    :param metric_ftns: list of per-example metric fns.
    :param config: ConfigParser.
    :param train_loader / valid_loader: ArrayDataLoader-compatible.
    :param len_epoch: if given, iteration-based training over an endless
        loader (reference trainer.py:21-27).
    :param mesh: device mesh; built from config when None.
    """

    def __init__(self, model, criterion, metric_ftns, config,
                 train_loader, valid_loader=None, len_epoch: Optional[int] = None,
                 mesh=None, seed: int = 0):
        # everything between the caller's config and a trainer that can
        # step; the first flight record's ``setup`` reads the ring from
        # where this span began
        with span("setup/trainer_init") as frame:
            self._setup_t0 = frame["t0"]
            self._build(model, criterion, metric_ftns, config,
                        train_loader, valid_loader, len_epoch, mesh, seed)

    def _build(self, model, criterion, metric_ftns, config, train_loader,
               valid_loader, len_epoch, mesh, seed) -> None:
        super().__init__(config)
        configure_debug(config["trainer"].get("debug"))
        # deterministic fault plan (resilience/faults): PDT_FAULTS env
        # wins over the ``trainer.faults`` config string; installed per
        # trainer build so one-shot faults re-arm for each fresh run
        faults.install_from_env_or_config(
            config["trainer"].get("faults")
        )
        # loader_raise targets the TRAIN input pipeline specifically —
        # the validation loader reaching the same batch ordinal first
        # must not consume the one-shot spec
        faults.watch_loader(train_loader)
        self._seed = int(seed)
        self.mesh = mesh if mesh is not None else mesh_from_config(config)
        model = inject_mesh(model, self.mesh)
        self.model = model
        self.criterion = criterion
        self.metric_ftns = list(metric_ftns)

        self.train_loader = train_loader
        tok_path = getattr(train_loader, "tokenizer_path", None)
        if tok_path is not None and dist.is_main_process():
            # pin the run's tokenizer IN the run dir: the corpus-side
            # cache is keyed by (file, vocab, train fraction) and a
            # later run can rewrite it, but generate.py must round-trip
            # prompts through the merges THIS run's embeddings saw
            # (data/tokenizer.tokenizer_from_config prefers this copy)
            import shutil

            try:
                shutil.copyfile(tok_path,
                                self.checkpoint_dir / "tokenizer.json")
            except OSError as e:  # non-fatal: corpus cache still works
                self.logger.warning("could not pin tokenizer: %s", e)
        if len_epoch is None:
            # config-level opt-in to iteration-based training (the
            # reference enables it by passing len_epoch to its Trainer;
            # here `trainer.len_epoch` in the JSON reaches the CLI path)
            len_epoch = config["trainer"].get("len_epoch")
        if len_epoch is None:
            self.len_epoch = len(train_loader)
            self._train_iter = None
        else:
            self.len_epoch = int(len_epoch)
            self._train_iter = iter(_endless_reshuffling(train_loader))
        self.valid_loader = valid_loader
        self.do_validation = valid_loader is not None
        self.log_step = max(int(np.sqrt(train_loader.batch_size)), 1)

        dk = config.get("data_keys", {}) or {}
        self.input_key = dk.get("input", "image")
        self.target_key = dk.get("target", "label")

        # --- optimizer + schedule (per-step, epoch-indexed; optim.py) ------
        self.tx, self.lr_fn, self.plateau = build_optimizer(
            config, self.len_epoch
        )

        # --- state init + placement (multi-host-legal jit creation; see
        # engine/state.create_sharded_train_state) --------------------------
        ema_decay = float(config["trainer"].get("ema_decay", 0.0))
        template = train_loader.arrays[self.input_key][:1]
        self._device_transform = getattr(
            train_loader, "device_transform", None
        )
        if self._device_transform is not None:
            # init must trace the model with the dtype it will actually
            # see (e.g. float32 after on-device uint8 normalization)
            template = np.asarray(
                self._device_transform({self.input_key: template})[
                    self.input_key
                ]
            )
        # the init program: trace, compile or cache read, run, placement
        with span("setup/state_init", seed=self._seed):
            self.state, self.state_sharding = create_sharded_train_state(
                model, self.tx, template,
                self.mesh, seed=seed, with_ema=ema_decay > 0,
            )
        self.batch_sharding = batch_sharding(self.mesh)
        if dist.is_main_process():
            self.logger.info(describe(model, self.state.params))

        # --- resume (reference base_trainer.py:48-49,134-163) -------------
        if config.resume is not None:
            self.state, self.start_epoch, restored_best = (
                self.ckpt_manager.restore(
                    config.resume, self.state, config.config,
                    type(model).__name__,
                )
            )
            if restored_best is not None:
                self.mnt_best = restored_best
            # step-accurate resume (resilience subsystem): the
            # data_state sidecar overrides the epoch-granular
            # ``meta.epoch + 1`` with the exact (epoch, next_batch)
            # the checkpointed state stopped at
            if bool(config["trainer"].get("step_accurate_resume", True)):
                self._apply_data_state(
                    CheckpointManager.load_data_state(config.resume)
                )
        elif config["trainer"].get("init_from"):
            # params-only warm start (``trainer.init_from`` in the JSON or
            # --set): graft matching param leaves from a checkpoint into
            # the fresh state — the transfer/LoRA-fine-tune primitive.
            # Unlike resume, optimizer state and epoch restart from zero.
            from ..checkpoint import warm_start_params

            params, restored, skipped = warm_start_params(
                config["trainer"]["init_from"], self.state.params
            )
            self.state = self.state.replace(
                params=params,
                # EMA shadows start at the warm-started weights, not at
                # the discarded fresh init (leaves are immutable jax
                # Arrays — sharing them is safe)
                **({"ema_params": params}
                   if self.state.ema_params is not None else {}),
            )
            self.logger.info(
                "Warm start from %s: %d param tensors restored, %d kept "
                "their init%s", config["trainer"]["init_from"],
                len(restored), len(skipped),
                (" (e.g. " + ", ".join(skipped[:3]) + ")") if skipped
                else "",
            )

        # host-side mirror of state.lr_scale (plateau LR control; survives
        # resume via the checkpointed state)
        replicated = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )
        self._replicate = jax.jit(lambda x: x, out_shardings=replicated)
        self._lr_scale_host = (
            float(jax.device_get(self.state.lr_scale))
            if self.state.lr_scale is not None else 1.0
        )
        if self.plateau is not None:
            self.plateau.scale = self._lr_scale_host
        self._plateau_warned = False

        # --- compile the hot loop -----------------------------------------
        grad_clip = config["trainer"].get("grad_clip_norm", 0.0)
        grad_accum = int(config["trainer"].get("grad_accum_steps", 1))
        self.skip_nonfinite = bool(
            config["trainer"].get("skip_nonfinite", False)
        )
        self.log_grad_norm = bool(
            config["trainer"].get("log_grad_norm", False)
        )
        # --- health summary (observability/health): a few scalar
        # reductions compiled INTO the step; fetched one step deferred,
        # so detection never syncs the dispatch pipeline ---------------
        health_cfg = config["trainer"].get("health", {}) or {}
        self._health_enabled = bool(health_cfg.get("enabled", True))
        self._health_keys = (
            health_metric_keys(self.state.params)
            if self._health_enabled else []
        )
        train_step = make_train_step(
            model, self.tx, criterion, self.metric_ftns,
            input_key=self.input_key, target_key=self.target_key,
            grad_clip_norm=grad_clip, grad_accum_steps=grad_accum,
            ema_decay=ema_decay, skip_nonfinite=self.skip_nonfinite,
            augment=build_augment(config["trainer"].get("augment")),
            mixup_alpha=float(config["trainer"].get("mixup_alpha", 0.0)),
            log_grad_norm=self.log_grad_norm,
            trainable_patterns=config["optimizer"].get("args", {}).get(
                "trainable"
            ),
            health=self._health_enabled,
            # in-graph deterministic fault (nan_grad@step:N), or None
            inject_nan_grad_step=faults.nan_grad_step(),
        )
        metric_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )
        train_keys = self._metric_keys() + (
            ["skipped_sum"] if self.skip_nonfinite else []
        ) + (["grad_norm_sum"] if self.log_grad_norm else []
             ) + self._health_keys + [
            f"{COUNTER_PREFIX}{name}_sum"
            for name in getattr(model, "step_counters", ())]
        # the options ride on the jitted function, so the warm-up's
        # ahead-of-time compile, the lazy first call and the profiler's
        # lower().compile() all build the same executable
        train_step_jit = jax.jit(
            train_step,
            donate_argnums=0,
            out_shardings=(self.state_sharding,
                           {k: metric_sharding for k in train_keys}),
            compiler_options=train_step_compile_options(self.mesh) or None,
        )
        eval_step = make_eval_step(
            model, criterion, self.metric_ftns,
            input_key=self.input_key, target_key=self.target_key,
            use_ema=ema_decay > 0
            and bool(config["trainer"].get("eval_with_ema", True)),
        )
        eval_step_jit = jax.jit(
            eval_step,
            out_shardings={
                k: metric_sharding for k in self._metric_keys()
            },
        )

        # --- background AOT warmup (engine/warmup.py): compile the steps
        # from abstract batches on a thread NOW, overlapping the rest of
        # init + first-epoch data startup, so step 1 dispatches a ready
        # executable instead of paying trace+compile inline. Any failure
        # degrades to the lazy jit path (warmup.result -> None). --------
        self._warmup = None
        if bool(config["trainer"].get("aot_warmup", True)):
            from .warmup import StepWarmup, abstract_batch

            try:
                warmup = StepWarmup()
                warmup.add(
                    "train_step", train_step_jit, self.state,
                    abstract_batch(train_loader, self.batch_sharding,
                                   transform=self._device_transform),
                )
                if valid_loader is not None:
                    warmup.add(
                        "eval_step", eval_step_jit, self.state,
                        abstract_batch(
                            valid_loader, self.batch_sharding,
                            transform=getattr(valid_loader,
                                              "device_transform", None),
                        ),
                    )
                self._warmup = warmup.start()
            except Exception:  # noqa: BLE001 — warmup is best-effort
                self.logger.warning(
                    "could not start AOT warmup; steps compile lazily",
                    exc_info=True,
                )
        self._train_step = instrument_step(
            train_step_jit, "train_step", warmup=self._warmup
        )
        self._eval_step = instrument_step(
            eval_step_jit, "eval_step", warmup=self._warmup
        )

        self.train_metrics = MetricTracker("loss", writer=self.writer)
        self.valid_metrics = MetricTracker(
            "loss", *[m.__name__ for m in self.metric_ftns], writer=self.writer
        )

        # --- profiling (SURVEY.md §5 tracing tier; reference had only the
        # steps_per_sec scalar) ---------------------------------------------
        prof_cfg = config["trainer"].get("profiler", {}) or {}
        self.profile_enabled = bool(prof_cfg.get("enabled", False))
        self.throughput = ThroughputMeter()          # log_step windows (TB)
        self.epoch_meter = ThroughputMeter()         # whole-epoch averages
        self.trace = TraceCapture(
            config.log_dir,
            start_step=prof_cfg.get("trace_start_step", 10),
            num_steps=prof_cfg.get("trace_steps", 0),
        )
        self._peak_flops = prof_cfg.get("peak_flops_per_device")
        self._flops_per_step = None  # measured lazily on the first batch
        # latch: the first-step meter reset (+ the profiler's one-time
        # AOT cost analysis) runs at most once per process
        self._first_step_timed = False
        # host->device transfer pipeline depth (data/loader.
        # prefetch_to_device): 2 double-buffers; deeper hides burstier
        # host gathers at the cost of depth x batch bytes of HBM
        self.prefetch_depth = max(
            int(config["trainer"].get("prefetch_depth", 2)), 1
        )

        # --- flight recorder (observability/telemetry): one structured
        # JSONL record per step in <run_dir>/telemetry.jsonl on process 0,
        # ring-buffered in memory everywhere (the watchdog's stall dump
        # reads the ring) -----------------------------------------------
        tel_cfg = config["trainer"].get("telemetry", {}) or {}
        self.recorder = FlightRecorder(
            run_dir=(self.checkpoint_dir
                     if dist.is_main_process()
                     and bool(tel_cfg.get("enabled", True)) else None),
            capacity=int(tel_cfg.get("capacity", 512)),
            memory_every=int(tel_cfg.get("memory_every", 16)),
        )
        # each iteration's account, and what the host was doing in a
        # stalled one (observability/telemetry.IterationAccount)
        self._account = IterationAccount(spans=get_span_recorder())
        self._record_ms = None  # the last record(), until a record takes it
        # anomaly detection over the deferred health summaries; dumps
        # (anomaly_<step>.json) on process 0 only, detection everywhere
        self.health = HealthMonitor(
            health_cfg, recorder=self.recorder,
            spans=get_span_recorder(),
            log_dir=(config.log_dir if dist.is_main_process() else None),
            layout=health_layout(self.state.params),
        )
        # per-log-window host stats exchange + straggler flag (no-op
        # collective single-host; auto-enabled on multi-host jobs)
        self.crosshost = CrossHostAggregator(
            tel_cfg.get("crosshost"), is_main=dist.is_main_process()
        )
        # runtime-triggered profiling (SIGUSR2 in train.py) notes its
        # captures on the flight-recorder timeline
        self.trace.attach_recorder(self.recorder)
        # tokens/step for LM data (integer [B, T] inputs): feeds the
        # per-record tokens field and the tokens/s aggregate. Exactly
        # rank 2 — integer image arrays (uint8 [B, H, W, C]) are not
        # token streams and must not emit a fake tokens_per_sec
        arr = train_loader.arrays.get(self.input_key)
        dtype = getattr(arr, "dtype", None)
        shape = getattr(arr, "shape", ())
        self._tokens_per_example = (
            int(shape[1])
            if dtype is not None and np.issubdtype(dtype, np.integer)
            and len(shape) == 2 else None
        )

        # hung-step detection (utils/watchdog.py); 0 disables. Wired to
        # the telemetry tier: a stall dumps active spans + the trailing
        # step records next to the faulthandler stacks.
        self.watchdog = StepWatchdog(
            timeout_s=float(config["trainer"].get("watchdog_secs", 0)),
            recorder=self.recorder,
            spans=get_span_recorder(),
            # file dump on process 0 only (same gating as the recorder's
            # JSONL above): hosts sharing a log dir must not race on one
            # stall_dump.json; every host still dumps stacks to stderr
            dump_path=(config.log_dir / "stall_dump.json"
                       if dist.is_main_process() else None),
            # supervisor liveness: the same beat the stall monitor uses
            # also touches the heartbeat file the resilience supervisor
            # watches from outside (PDT_HEARTBEAT_FILE exported by
            # scripts/supervise.py; trainer.heartbeat_file otherwise)
            heartbeat_path=(os.environ.get("PDT_HEARTBEAT_FILE")
                            or config["trainer"].get("heartbeat_file")),
        )

    def _record(self, step: int, rec: dict) -> None:
        """One flight record, timed: the JSON line, and host RSS and the
        devices' memory every sixteenth. ``record_ms`` rides the next
        record built, whose ``wall_ms`` holds it."""
        t_call = time.perf_counter()
        self.recorder.record(step, **rec)
        self._record_ms = (time.perf_counter() - t_call) * 1e3

    def _setup_facts(self, first_iteration_s: float) -> dict:
        """This trainer's set-up by phase, for the first flight record:
        ``setup`` is seconds inside each ``setup/*``, ``warmup/*`` and
        ``*/await_warmup`` span that has finished since this trainer's
        ``setup/trainer_init`` began (a process may build many), with
        ``first_iteration_s`` (the loop's first data wait to the first
        step's results on the host; it holds the await); ``setup_at``
        is each phase's start in seconds after ``setup/trainer_init``'s,
        so the record alone shows how far the warm-up's thread ran
        beside this one."""
        events = [e for e in get_span_recorder().since(self._setup_t0)
                  if e["name"].startswith(("setup/", "warmup/"))
                  or e["name"].endswith("/await_warmup")]
        origin = min((e["ts"] for e in events), default=0.0)
        setup: dict = {}
        at: dict = {}
        for e in events:
            name = e["name"]
            setup[name] = setup.get(name, 0.0) + e["dur"] / 1e6
            at[name] = min(at.get(name, math.inf), (e["ts"] - origin) / 1e6)
        return {
            "setup": {**{k: round(v, 6) for k, v in setup.items()},
                      "first_iteration_s": round(first_iteration_s, 6)},
            "setup_at": {k: round(v, 6) for k, v in at.items()},
        }

    def _step_program_facts(self, batch) -> dict:
        """Where the first batch and the params really live — on the
        first flight record, so a multi-chip run can be checked from its
        telemetry (code that has only ever seen one chip may put
        everything on the first). Until PR 38 it also counted two
        strings in the compiled step's text: 1.4 s of a 36-layer run's
        start to make 20 MB of text."""
        def n_devices(tree):
            return len({s.device for leaf in jax.tree.leaves(tree)
                        for s in leaf.addressable_shards})

        return {"batch_devices": n_devices(batch),
                "param_devices": n_devices(self.state.params)}

    def _metric_keys(self):
        return ["loss_sum", "count"] + [
            f"{m.__name__}_sum" for m in self.metric_ftns
        ]

    # -- resilience: step-accurate resume -----------------------------------

    def _apply_data_state(self, ds: Optional[dict]) -> None:
        """Turn a checkpoint's ``data_state`` sidecar into a mid-epoch
        resume point: ``start_epoch`` becomes the in-flight epoch and
        ``_batches`` fast-forwards its first epoch to ``next_batch``.
        Falls back (with a warning) to the epoch-granular semantics
        when the sidecar is absent, the run is iteration-based
        (endless loader: batch ordinals are not stable coordinates),
        or the data geometry changed under the checkpoint."""
        if not ds:
            return
        if self._train_iter is not None:
            self.logger.warning(
                "data_state present but len_epoch (iteration-based) "
                "training resumes at epoch granularity."
            )
            return
        if (int(ds.get("len_epoch", self.len_epoch)) != self.len_epoch
                or int(ds.get("batch_size",
                              self.train_loader.batch_size))
                != self.train_loader.batch_size):
            self.logger.warning(
                "data_state geometry mismatch (checkpoint len_epoch=%s/"
                "batch_size=%s vs current %s/%s); resuming at epoch "
                "granularity.", ds.get("len_epoch"), ds.get("batch_size"),
                self.len_epoch, self.train_loader.batch_size,
            )
            return
        epoch = int(ds.get("epoch", self.start_epoch))
        next_batch = int(ds.get("next_batch", 0))
        if next_batch >= self.len_epoch:  # normalized at save, but be safe
            epoch, next_batch = epoch + 1, 0
        self.start_epoch = epoch
        self._resume_next_batch = next_batch
        if next_batch and dist.is_main_process():
            self.logger.info(
                "Step-accurate resume: continuing epoch %d at batch %d "
                "(global step %s).", epoch, next_batch,
                ds.get("global_step", "?"),
            )

    # -- epoch loops --------------------------------------------------------

    def _batches(self, epoch: int):
        # mid-epoch fast-forward applies exactly once: to the resumed
        # epoch itself (the ordinal skip is exact because the epoch
        # permutation is a pure function of (seed, epoch))
        skip = (self._resume_next_batch
                if epoch == self.start_epoch else 0)
        if self._train_iter is not None:
            for i in range(self.len_epoch):
                yield i, next(self._train_iter)
        else:
            self.train_loader.set_epoch(epoch)
            if skip and hasattr(self.train_loader, "iter_batches"):
                it = self.train_loader.iter_batches(start_batch=skip)
            else:
                it = iter(self.train_loader)
                for _ in range(skip):  # generic-iterable fallback
                    next(it, None)
            yield from enumerate(it, start=skip)

    def _train_epoch(self, epoch: int) -> dict:
        self.train_metrics.reset()
        self.health.epoch_start()  # promotion pause is epoch-scoped
        self.throughput.reset()  # exclude validation/checkpoint wall time
        self.epoch_meter.reset()  # (epoch 1 includes compile unless the
        # profiler's post-compile reset fires; later epochs are clean)
        accum = None
        batches = (b for _, b in self._batches(epoch))
        depth = int(self.config["trainer"].get("host_prefetch", 2))
        if depth > 0:
            batches = host_prefetch(batches, depth)
        prefetched = prefetch_to_device(batches, self.batch_sharding,
                                        size=self.prefetch_depth,
                                        transform=self._device_transform)
        main = dist.is_main_process()
        if main:
            # reference trainer/trainer.py:45 wraps the hot loop in tqdm;
            # auto-gated on a TTY (or trainer.progress true/false)
            prefetched = maybe_tqdm(
                prefetched, total=self.len_epoch,
                desc=f"train {epoch}",
                enable=self.config["trainer"].get("progress"),
            )
        # Mid-epoch preemption polling: the SIGTERM notice window (~30s on
        # cloud TPUs) is far shorter than an ImageNet epoch, so waiting for
        # the epoch edge would forfeit the save. Single-host polls the free
        # local flag every batch; multi-host polls the consensus collective
        # every preempt_check_steps batches so every host breaks at the
        # SAME batch (a lone early exit would hang peers' collectives).
        check_every = max(
            int(self.config["trainer"].get("preempt_check_steps", 100)), 1
        )
        single_host = dist.process_count() == 1
        preempted = False  # consensus result: identical on every host
        # idempotent; trainer.watchdog_secs must exceed the first-step
        # compile time or epoch 1 will false-alarm
        self.watchdog.start()
        batches_it = iter(prefetched)
        # resumed mid-epoch: batch ordinals continue from the resume
        # point (the generator under `batches` already fast-forwarded)
        start_batch = (self._resume_next_batch
                       if epoch == self.start_epoch else 0)
        self._cursor = (epoch, start_batch)
        batch_idx = start_batch - 1
        # Log-step metric fetches are DEFERRED by one log window: the
        # entry enqueued at step N is completed at step N + log_step,
        # when its device buffers have long resolved, so the fetch of
        # the metrics does not wait for the step just dispatched. (The
        # flush still does, under train/log_lr: see _flush_log_entry.)
        # Holds at most one entry (a handful of scalar metric buffers).
        pending_log = deque()
        log_flush_ms = None  # the last flush, until a record takes it
        t_iter = time.perf_counter()
        while True:
            # data-wait = time blocked on the prefetch pipeline; near
            # zero when prefetch hides the gather, the whole step time
            # when the loader is the bottleneck — the telemetry field
            # that answers "is this run input-bound?"
            t_wait = time.perf_counter()
            with span("data/next_batch"):
                try:
                    batch = next(batches_it)
                except StopIteration:
                    break
            data_wait_ms = (time.perf_counter() - t_wait) * 1e3
            batch_idx += 1
            step = (epoch - 1) * self.len_epoch + batch_idx
            # deterministic fault hook (resilience/faults): slow_host /
            # crash / kill fire HERE, before the step dispatches, so
            # kill@step:N means exactly N completed steps
            faults.on_step(step)
            self.trace.before_step(step)
            # its span is instrument_step's (train_step/dispatch, or
            # train_step/compile+execute on a lazy first call)
            t_call = time.perf_counter()
            self.state, m = self._train_step(self.state, batch)
            dispatch_ms = (time.perf_counter() - t_call) * 1e3
            # the dispatched step completes on-device even if the host
            # dies after this point: the cursor counts it done
            self._cursor = (epoch, batch_idx + 1)
            self.trace.after_step(step, sync=m)
            self.watchdog.beat()
            health_fetch_ms = None
            if self._health_keys:
                # strip the health scalars out of the epoch accumulator
                # (they are per-step signals, not sufficient statistics)
                # and hand them to the monitor, which fetches them one
                # step deferred — no sync on the step just dispatched
                hm = {k: m.pop(k) for k in self._health_keys if k in m}
                t_call = time.perf_counter()
                # enqueues this step's summary and fetches the one
                # queued before it
                with span("train/health_fetch", step=step):
                    self.health.enqueue(
                        step, hm,
                        meta={"epoch": epoch, "batch_idx": batch_idx},
                    )
                health_fetch_ms = (time.perf_counter() - t_call) * 1e3
            self.throughput.update(self.train_loader.batch_size)
            self.epoch_meter.update(self.train_loader.batch_size)
            # per-step flight record; wall_ms is the full loop iteration
            # (dispatch + donation backpressure + data wait), so summed
            # wall time over a window is the honest steps/s denominator.
            # dispatch_ms, health_fetch_ms and log_flush_ms say where it
            # went, each on the record of the iteration whose wall_ms
            # holds it: a log flush runs after this record's clock has
            # been read, so it is the next record's, and so is record_ms,
            # the recorder's own write. unattributed_ms closes the sum:
            # the fault hook, the watchdog's beat, the meters, the
            # preemption poll, the recorder's write
            # (self._account.settle, below)
            rec = {
                "wall_ms": round((time.perf_counter() - t_iter) * 1e3, 3),
                "data_wait_ms": round(data_wait_ms, 3),
                "dispatch_ms": round(dispatch_ms, 3),
                "examples": self.train_loader.batch_size,
            }
            t_iter = time.perf_counter()
            # a capture's start or stop (which writes the trace:
            # seconds), on the iterations that hold one
            profile_ms = self.trace.take_ms()
            if profile_ms is not None:
                rec["profile_ms"] = round(profile_ms, 3)
            if health_fetch_ms is not None:
                rec["health_fetch_ms"] = round(health_fetch_ms, 3)
            if log_flush_ms is not None:
                rec["log_flush_ms"] = round(log_flush_ms, 3)
                log_flush_ms = None
            if self._record_ms is not None:
                rec["record_ms"] = round(self._record_ms, 3)
                self._record_ms = None
            stall = self._account.settle(
                rec, first=not self._first_step_timed)
            if stall is not None:
                self.logger.warning(
                    "Iteration stalled at step %d: %.1f ms, %.1f over the "
                    "trailing median, most of it in %s (gc %.1f ms, %d of "
                    "generation 2; context switches %d voluntary, %d "
                    "involuntary; major page faults %d; other threads "
                    "inside %s)", step, rec["wall_ms"], stall["over_ms"],
                    stall["in"], stall["gc_ms"], stall["gc_gen2"],
                    stall["nvcsw"], stall["nivcsw"], stall["majflt"],
                    stall["threads"] or "no span",
                )
            if self._tokens_per_example:
                rec["tokens"] = (self._tokens_per_example
                                 * self.train_loader.batch_size)

            if not self._first_step_timed:
                # The run's first step carries the compile (or the AOT
                # warm-install) cost: exclude it from steady-state
                # meters UNCONDITIONALLY — this used to happen only
                # under the profiler, so unprofiled runs reported a
                # steps_per_sec that silently averaged in the compile
                # step. (Keyed on the latch alone, not batch_idx == 0:
                # a step-accurate resume enters mid-epoch, where the
                # first — compiling — step has a nonzero ordinal.)
                self._first_step_timed = True
                if self.profile_enabled:
                    # ONE AOT lower+compile of the step, for the FLOPs
                    # probe (None when the backend reports none; the
                    # latch stays set, and profiling never breaks the
                    # step loop)
                    self._flops_per_step = compiled_flops(
                        self._train_step, self.state, batch)
                jax.block_until_ready(m)
                rec["step_program"] = self._step_program_facts(batch)
                rec.update(self._setup_facts(
                    rec["wall_ms"] / 1e3 + time.perf_counter() - t_iter))
                self.throughput.reset()  # exclude compilation from rates
                self.epoch_meter.reset()

            accum = m if accum is None else jax.tree.map(jnp.add, accum, m)

            if self.crosshost.should_exchange(batch_idx, self.log_step):
                # EVERY host reaches this collective at the same batch
                # (deterministic condition); only process 0 attaches the
                # aggregate to its record
                agg = self.crosshost.exchange(
                    self.recorder.last(self.log_step)
                )
                if agg is not None and main:
                    rec["hosts"] = agg["hosts"]
                    if "wall_spread" in agg:
                        rec["wall_spread"] = agg["wall_spread"]
                    if agg.get("straggler"):
                        rec["straggler"] = True
                        rec["straggler_hosts"] = agg["straggler_hosts"]

            if main and batch_idx % self.log_step == 0:
                # deferred fetch: complete the PREVIOUS log window's
                # entry (its step finished while this window's steps
                # dispatched), enqueue this one; only the TB image grid
                # needs the live batch, so it logs at enqueue time.
                # Compile events drain NOW so this step's own compile
                # (the lazy first-step case) rides under its own step
                # id, not whichever record happens to flush next
                if pending_log:
                    t_call = time.perf_counter()
                    self._flush_log_entry(pending_log.popleft())
                    log_flush_ms = (time.perf_counter() - t_call) * 1e3
                events = drain_compile_events()
                if events:
                    rec["compile_events"] = events
                self.writer.set_step(step)
                self._log_input_images(batch)
                pending_log.append((step, epoch, batch_idx, m, rec))
            else:
                self._record(step, rec)

            if ((single_host or (batch_idx + 1) % check_every == 0)
                    and preemption.sync_requested()):
                preempted = True
                if main:
                    self.logger.warning(
                        "Preemption signal: breaking epoch %d at batch %d "
                        "(partial epoch will be checkpointed).",
                        epoch, batch_idx + 1,
                    )
                break

            if (self.save_interval_steps
                    and (batch_idx + 1) % self.save_interval_steps == 0):
                # A/B-slot async save: the step loop continues while the
                # write flushes in the background (no wait() here)
                self.ckpt_manager.save_interval(
                    epoch=epoch, step=batch_idx + 1, state=self.state,
                    arch=type(self.model).__name__,
                    config=dict(self.config.config),
                    monitor_best=(
                        self.mnt_best
                        if isinstance(self.mnt_best, (int, float)) else 0.0
                    ),
                    data_state=self._data_state_snapshot(),
                )
                if main:
                    self.logger.info(
                        "Interval checkpoint at epoch %d batch %d.",
                        epoch, batch_idx + 1,
                    )

        while pending_log:
            # drain the deferred log entry (epoch end syncs anyway via
            # finalize_metrics below, so this fetch costs nothing extra)
            self._flush_log_entry(pending_log.popleft())
        self.health.drain()  # observe the last step's deferred summary

        log = (
            finalize_metrics(jax.tree.map(float, accum)) if accum else {}
        )
        # whole-epoch throughput (the finalize_metrics float() above synced
        # the device, so the window is honest); + MFU when the profiler
        # measured the compiled step's FLOPs
        if log:
            rate = self.epoch_meter.rate()
            log["examples_per_sec"] = round(rate["examples_per_sec"], 1)
            util = mfu(self._flops_per_step, rate["steps_per_sec"],
                       peak_per_device=self._peak_flops)
            if util is not None:
                log["mfu"] = round(util, 4)
        # Keep the tracker's smoothed loss for TB parity, but report the
        # exact global epoch averages. A preempted epoch skips validation —
        # the SIGTERM notice window is for checkpointing, not eval.
        if self.do_validation and not preempted:
            with span("train/validate", epoch=epoch):
                val_log = self._valid_epoch(epoch)
            log.update(**{f"val_{k}": v for k, v in val_log.items()})
        # a preempted epoch skipped validation, so the monitored key is
        # legitimately absent — not a plateau decision and not a misconfig
        if self.plateau is not None and not preempted:
            self._plateau_step(log)
        return log

    def _flush_log_entry(self, entry) -> None:
        """Complete one deferred log-step record.

        Called one log window after the entry's step was dispatched —
        by then ``log_step`` further steps have been queued behind it,
        so ``jax.device_get`` under ``train/log_fetch`` reads buffers
        that have resolved. The flush is not free of the device all the
        same: the schedule under ``train/log_lr`` is jnp arithmetic,
        whose small programs queue behind the step dispatched just
        before, so ``float()`` there waits that step out and the device
        then idles until the next dispatch (``idle_log_flush_ms``,
        1-2 ms a step on the chip; PERF.md section 5). The entry's
        flight record lands in the JSONL one window late but under its
        own step id; window throughput is dispatch-rate (bounded-queue
        steady state tracks completion rate; epoch numbers still come
        from the synced ``finalize_metrics`` path).
        """
        step, epoch, batch_idx, m, rec = entry
        with span("train/log", step=step):
            # the wait, if there is one, apart from the arithmetic and
            # the writer calls after it
            with span("train/log_fetch", step=step):
                m = jax.device_get(m)
            self.writer.set_step(step)
            loss_val = (float(m["loss_sum"])
                        / max(float(m["count"]), 1.0))
            self.train_metrics.update("loss", loss_val)
            # the schedule is jnp arithmetic: its small programs queue
            # on the device behind the step dispatched just before
            with span("train/log_lr", step=step):
                lr_val = float(self.lr_fn(step)) * self._lr_scale_host
            self.writer.add_scalar("lr", lr_val)
            rec["loss"] = round(loss_val, 6)
            rec["lr"] = lr_val
            if self.log_grad_norm:
                rec["grad_norm"] = round(
                    float(m["grad_norm_sum"])
                    / max(float(m["count"]), 1.0), 6,
                )
            # the model's own counters of this step (steps.COUNTER_PREFIX),
            # fetched with the loss above
            for key, value in m.items():
                if key.startswith(COUNTER_PREFIX):
                    rec[key[len(COUNTER_PREFIX):-len("_sum")]] = round(
                        float(value) / max(float(m["count"]), 1.0), 6)
            if self.profile_enabled and step > 0:
                rate = self.throughput.rate()
                self.writer.add_scalar(
                    "examples_per_sec", rate["examples_per_sec"]
                )
                rec["steps_per_sec"] = round(
                    rate["steps_per_sec"], 4)
                rec["examples_per_sec"] = round(
                    rate["examples_per_sec"], 1)
                if self._tokens_per_example:
                    rec["tokens_per_sec"] = round(
                        rate["examples_per_sec"]
                        * self._tokens_per_example, 1)
                util = mfu(self._flops_per_step,
                           rate["steps_per_sec"],
                           peak_per_device=self._peak_flops)
                if util is not None:
                    self.writer.add_scalar("mfu", util)
                    rec["mfu"] = round(util, 4)
            self.logger.debug(
                "Train Epoch: %d %s Loss: %.6f",
                epoch, self._progress(batch_idx + 1), loss_val,
            )
        hc = health_counters()
        if hc["anomaly_total"]:
            rec["anomaly_total"] = hc["anomaly_total"]
        if hc["straggler_windows_total"]:
            rec["straggler_windows_total"] = hc["straggler_windows_total"]
        self._record(step, rec)

    def _plateau_step(self, log: dict) -> None:
        """Per-epoch ReduceLROnPlateau update of ``state.lr_scale``.

        Runs identically on every host (epoch metrics are global
        reductions), so the replicated scalar stays consistent without a
        collective. The jit identity makes the new value a born-global
        array (legal multi-host, like create_sharded_train_state).
        """
        value = log.get(self.plateau.monitor)
        if value is None:
            # typo'd monitor key or validation disabled: say so once instead
            # of silently training at full LR forever (mirrors the trainer's
            # monitor-metric-not-found warning)
            if not self._plateau_warned and dist.is_main_process():
                self.logger.warning(
                    "Warning: ReduceLROnPlateau monitor '%s' not found in "
                    "epoch metrics %s; plateau LR scheduling is inactive.",
                    self.plateau.monitor, sorted(log),
                )
            self._plateau_warned = True
            return
        # NaN/inf flows into the controller: comparisons with NaN are False,
        # so it counts as a bad epoch — exactly torch's behavior (and the
        # LR drop it triggers is often what rescues a diverging run)
        new_scale = self.plateau.step(float(value))
        if new_scale != self._lr_scale_host:
            if dist.is_main_process():
                self.logger.info(
                    "ReduceLROnPlateau: %s did not improve for %d epochs; "
                    "lr scale %.3g -> %.3g",
                    self.plateau.monitor, self.plateau.patience + 1,
                    self._lr_scale_host, new_scale,
                )
            self._lr_scale_host = new_scale
            self.state = self.state.replace(
                lr_scale=self._replicate(np.float32(new_scale))
            )

    def _valid_epoch(self, epoch: int) -> dict:
        """Validation with in-graph global reduction (vs reference's pickle
        gather of the full prediction set, trainer.py:75-88)."""
        self.valid_metrics.reset()
        if hasattr(self.valid_loader, "set_epoch"):
            self.valid_loader.set_epoch(epoch)
        accum = None
        val_batches = prefetch_to_device(
            self.valid_loader, self.batch_sharding,
            size=self.prefetch_depth,
            transform=getattr(self.valid_loader, "device_transform", None),
        )
        if dist.is_main_process():
            val_batches = maybe_tqdm(
                val_batches, total=len(self.valid_loader),
                desc=f"valid {epoch}",
                enable=self.config["trainer"].get("progress"),
            )
        for batch in val_batches:
            m = self._eval_step(self.state, batch)
            accum = m if accum is None else jax.tree.map(jnp.add, accum, m)
            self.watchdog.beat()
        result = finalize_metrics(jax.tree.map(float, accum)) if accum else {}
        if dist.is_main_process():
            self.writer.set_step(epoch * self.len_epoch, mode="valid")
            for k, v in result.items():
                self.valid_metrics.update(k, v)
        return result

    # -- checkpointing ------------------------------------------------------

    def _save_checkpoint(self, epoch: int, save_best: bool = False) -> None:
        if save_best and not self.health.promotion_allowed():
            # trainer.health.pause_best_promotion: an epoch that fired a
            # numerics anomaly does not crown model_best — its monitored
            # metric may be the artifact of the very step that fired
            save_best = False
            if dist.is_main_process():
                self.logger.warning(
                    "Health: anomaly at step %s this epoch; best-model "
                    "promotion skipped for epoch %d "
                    "(health.pause_best_promotion).",
                    self.health.last_anomaly_step, epoch,
                )
        self.ckpt_manager.save(
            epoch=epoch,
            state=self.state,
            arch=type(self.model).__name__,
            config=dict(self.config.config),
            monitor_best=(
                self.mnt_best if isinstance(self.mnt_best, (int, float)) else 0.0
            ),
            save_best=save_best,
            # completed epoch ⇒ (epoch+1, batch 0); preemption-cut
            # epoch ⇒ the exact mid-epoch next batch (the cursor knows)
            data_state=self._data_state_snapshot(),
        )
        keep = int(self.config["trainer"].get("keep_last", 0))
        if keep > 0:
            self.ckpt_manager.prune(keep)

    # -- misc ---------------------------------------------------------------

    def _log_input_images(self, batch) -> None:
        """TB input grid (reference trainer.py:69 make_grid) for image data."""
        x = batch.get(self.input_key)
        if x is None or x.ndim != 4 or self.writer.writer is None:
            return
        imgs = np.asarray(jax.device_get(x[:8])).astype(np.float32)
        lo, hi = imgs.min(), imgs.max()
        imgs = (imgs - lo) / max(hi - lo, 1e-6)
        grid = np.concatenate(list(imgs), axis=1)  # [H, 8*W, C]
        self.writer.add_image("input", grid, dataformats="HWC")

    def _progress(self, batch_idx: int) -> str:
        current = batch_idx * self.train_loader.batch_size
        total = getattr(self.train_loader, "n_samples", self.len_epoch)
        if self._train_iter is not None:
            current, total = batch_idx, self.len_epoch
        return f"[{current}/{total} ({100.0 * current / total:.0f}%)]"
