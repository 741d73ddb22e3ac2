#!/usr/bin/env python
"""Training entry point.

CLI parity with /root/reference/train.py:77-98 (flags -c/-r/-l/-s/
--no-validate/--seed/--deterministic plus --lr/--bs keychain overrides).
Differences, by design:
- no launcher: one process per *host* (TPU runtime), devices come from the
  mesh — ``torch.distributed.launch`` has no analogue;
- ``-l/--local_rank`` is accepted and ignored (device binding is XLA's job);
- ``--bs`` targets ``train_loader;args;batch_size`` (the reference targets a
  ``data_loader`` block absent from its own configs — latent bug, SURVEY.md
  §2.1).
"""
import argparse
import collections
import os

from pytorch_distributed_template_tpu.config import (
    ConfigParser, LOADERS, METRICS, MODELS,
)
from pytorch_distributed_template_tpu import data, models  # noqa: F401  (register)
from pytorch_distributed_template_tpu.engine import Trainer
from pytorch_distributed_template_tpu.engine.losses import resolve_loss
from pytorch_distributed_template_tpu.parallel import dist, mesh_from_config
from pytorch_distributed_template_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(args, config):
    logger = config.get_logger("train")

    # persistent XLA compile cache (config["compile_cache"]): before any
    # jit so re-runs skip step-1 compilation entirely
    configure_compile_cache(config)

    # multi-host init (no-op single host; reference train.py:20-29)
    dist.initialize()

    mesh = mesh_from_config(config)
    if dist.is_main_process():
        logger.info(
            "mesh: %s over %d devices (%d hosts)",
            dict(mesh.shape), mesh.size, dist.process_count(),
        )

    model = config.init_obj("arch", MODELS)
    criterion = resolve_loss(config["loss"])
    metric_fns = [METRICS.get(m) for m in config["metrics"]]

    train_loader = config.init_obj("train_loader", LOADERS)
    valid_loader = (
        None if args.no_validate else config.init_obj("valid_loader", LOADERS)
    )

    trainer = Trainer(
        model, criterion, metric_fns,
        config=config,
        train_loader=train_loader,
        valid_loader=valid_loader,
        mesh=mesh,
        seed=args.seed if args.seed is not None else 0,
    )

    # on-demand profiling: `kill -USR2 <pid>` captures the next N steps
    # (PDT_PROFILE_STEPS, default 5) as a jax.profiler trace into
    # <log_dir>/profile — no restart, no config edit
    from pytorch_distributed_template_tpu.observability.profiler import (
        install_sigusr2,
    )

    if install_sigusr2(trainer.trace) and dist.is_main_process():
        logger.info(
            "SIGUSR2 armed: signal pid %d to capture an on-demand "
            "profiler trace (PDT_PROFILE_STEPS=%s steps).",
            os.getpid(), os.environ.get("PDT_PROFILE_STEPS", "5"),
        )

    trainer.train()

    from pytorch_distributed_template_tpu.resilience import EXIT_PREEMPTED
    from pytorch_distributed_template_tpu.utils import preemption

    if preemption.requested():
        # checkpointed + drained, but the work is NOT finished: exit
        # with the distinct preemption code so the supervisor
        # (scripts/supervise.py) relaunches without burning its crash
        # budget — a plain shell still sees non-zero
        logger.warning("exiting with preemption status %d (resume "
                       "with --auto-resume)", EXIT_PREEMPTED)
        raise SystemExit(EXIT_PREEMPTED)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="TPU-native training template")
    parser.add_argument("-c", "--config", default=None, type=str,
                        help="config file path (default: None)")
    parser.add_argument("-r", "--resume", default=None, type=str,
                        help="path to latest checkpoint (default: None)")
    parser.add_argument("-l", "--local_rank", default=0, type=int,
                        help="accepted for launcher compatibility; unused on TPU")
    parser.add_argument("-s", "--save_dir", default=None, type=str,
                        help="dir of save path")
    parser.add_argument("--no-validate", action="store_true",
                        help="skip validation during training")
    parser.add_argument("--auto-resume", action="store_true",
                        help="resume from the experiment's newest checkpoint "
                             "if one exists (relaunch-after-preemption)")
    parser.add_argument("--seed", type=int, default=None, help="Random seed.")
    parser.add_argument("--deterministic", action="store_true",
                        help="accepted for parity; TPU/XLA runs are "
                             "deterministic by construction given a seed")

    CustomArgs = collections.namedtuple("CustomArgs", "flags type target")
    options = [
        CustomArgs(["--lr", "--learning_rate"], type=float,
                   target="optimizer;args;lr"),
        CustomArgs(["--bs", "--batch_size"], type=int,
                   target="train_loader;args;batch_size"),
    ]
    args, config = ConfigParser.from_args(parser, options, training=True)
    main(args, config)
