"""Merge trained LoRA adapters into a plain dense serving artifact.

The closing step of the parameter-efficient fine-tuning workflow
(models/lora.py): after ``train.py`` with ``arch.args.lora_rank`` +
``optimizer.args.trainable: ["lora_"]`` + ``trainer.init_from``, this
folds ``kernel + (alpha / rank) * A @ B`` into dense kernels and writes
a params-only serving artifact — the merged model costs nothing extra
at inference and can be further quantized:

    python scripts/merge_lora.py -r saved/<ft>/train/<run>/model_best
    python generate.py -r saved/<ft>/.../serving_merged/model_merged ...
    # optional: int8-quantize the MERGED artifact's dense weights
    python scripts/quantize_checkpoint.py \
        -r saved/<ft>/.../serving_merged/model_merged

The artifact's ``config.json`` strips ``lora_rank`` from the arch args
(and ``trainable``/``init_from`` from the optimizer/trainer blocks), so
resume rediscovery builds the plain dense model.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from pytorch_distributed_template_tpu.checkpoint import (  # noqa: E402
    save_serving_params,
)
from pytorch_distributed_template_tpu.config import (  # noqa: E402
    ConfigParser, MODELS,
)
import pytorch_distributed_template_tpu.data  # noqa: F401,E402 (registries)
import pytorch_distributed_template_tpu.engine  # noqa: F401,E402
import pytorch_distributed_template_tpu.models  # noqa: F401,E402
from pytorch_distributed_template_tpu.engine.evaluator import (  # noqa: E402
    restore_template_state,
)
from pytorch_distributed_template_tpu.models.base import (  # noqa: E402
    inject_mesh,
)
from pytorch_distributed_template_tpu.models.lora import (  # noqa: E402
    merge_lora_params,
)
from pytorch_distributed_template_tpu.parallel import (  # noqa: E402
    dist, mesh_from_config,
)


def main(args, config):
    logger = config.get_logger("merge_lora")
    assert config.resume is not None, "merging requires a checkpoint (-r)"

    arch_args = config["arch"].get("args", {})
    rank = int(arch_args.get("lora_rank", 0))
    if rank <= 0:
        raise SystemExit(
            "checkpoint's arch has no lora_rank — nothing to merge"
        )
    alpha = float(arch_args.get("lora_alpha", 16.0))

    dist.initialize()
    mesh = mesh_from_config(config)
    model = inject_mesh(config.init_obj("arch", MODELS), mesh)
    state, _ = restore_template_state(config, model, mesh)
    src = "ema_params" if args.ema and state.ema_params is not None \
        else "params"
    merged = merge_lora_params(jax.device_get(getattr(state, src)),
                               alpha=alpha)

    out_dir = (
        config.resume.parent / "serving_merged"
        if args.output is None else Path(args.output)
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    serving_cfg = copy.deepcopy(config.config)
    sargs = serving_cfg.setdefault("arch", {}).setdefault("args", {})
    sargs.pop("lora_rank", None)
    sargs.pop("lora_alpha", None)
    serving_cfg.get("optimizer", {}).get("args", {}).pop("trainable", None)
    serving_cfg.get("trainer", {}).pop("init_from", None)
    (out_dir / "config.json").write_text(json.dumps(serving_cfg, indent=2))

    path = save_serving_params(
        out_dir / "model_merged", merged,
        meta={
            "arch": type(model).__name__,
            "lora_merged": {"rank": rank, "alpha": alpha},
            "source": str(config.resume),
            "source_params": src,
        },
    )
    logger.info("Merged rank-%d LoRA (alpha=%s) from %s -> %s",
                rank, alpha, config.resume, path)
    print(path)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="Merge LoRA adapters into a dense serving artifact"
    )
    parser.add_argument("-c", "--config", default=None, type=str)
    parser.add_argument("-r", "--resume", required=True, type=str,
                        help="LoRA training checkpoint directory.")
    parser.add_argument("-s", "--save_dir", default=None, type=str)
    parser.add_argument("-o", "--output", default=None, type=str,
                        help="Artifact directory (default: "
                             "<checkpoint_parent>/serving_merged).")
    parser.add_argument("--ema", action="store_true",
                        help="Merge the EMA shadow weights if present.")
    args, config = ConfigParser.from_args(parser, (), training=False)
    main(args, config)
