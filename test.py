#!/usr/bin/env python
"""Distributed evaluation entry point.

CLI parity with /root/reference/test.py:104-128: requires ``-r`` (the config
is rediscovered next to the checkpoint), evaluates the ``test_loader`` over
the full mesh, reports loss + metrics over the global dataset.
"""
import argparse

from pytorch_distributed_template_tpu.config import ConfigParser
from pytorch_distributed_template_tpu import data, models  # noqa: F401  (register)
from pytorch_distributed_template_tpu.engine.evaluator import evaluate
from pytorch_distributed_template_tpu.parallel import dist
from pytorch_distributed_template_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(args, config):
    configure_compile_cache(config)
    dist.initialize()
    evaluate(config, save_outputs=args.save_outputs, seed=args.seed)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="TPU-native evaluation")
    parser.add_argument("-c", "--config", default=None, type=str,
                        help="optional config overlay (fine-tune style)")
    parser.add_argument("-r", "--resume", required=True, type=str,
                        help="checkpoint directory to evaluate")
    parser.add_argument("-l", "--local_rank", default=0, type=int,
                        help="accepted for launcher compatibility; unused")
    parser.add_argument("-s", "--save_dir", default=None, type=str)
    parser.add_argument("--seed", type=int, default=None,
                        help="seed eval-time model randomness (the "
                             "'eval' rng stream, e.g. BertMLM's random "
                             "eval mask); default: deterministic eval")
    parser.add_argument("--save-outputs", default=None, type=str,
                        metavar="DIR",
                        help="dump per-example outputs/targets (npy) here "
                             "in addition to metrics")

    args, config = ConfigParser.from_args(parser, (), training=False)
    main(args, config)
