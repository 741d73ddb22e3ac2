"""Quantize a trained checkpoint into an int8 (w8a16) serving artifact.

Completes the serving workflow at the CLI level (the reference has no
serving path at all — its ``test.py`` is batch evaluation only,
/root/reference/test.py:64-101):

    python train.py -c configs/bytelm_stdlib.json
    python scripts/quantize_checkpoint.py -r saved/<...>/model_best
    python generate.py -r saved/<...>/serving_w8a16/model_w8a16 \
        --prompt "def main(" --max-new-tokens 128

The artifact directory holds a ``config.json`` whose arch args carry
``quant: "w8a16"`` (so ConfigParser's resume rediscovery builds the
quant model with no extra flags) and a params-only orbax tree with int8
kernels + per-channel scales (models/quant.quantize_params_w8). The
sampling CLI detects the ``params_only`` sidecar and skips the
TrainState template. KV-cache quantization stays a serving-time choice:
add ``--set "arch;args;kv_quant" int8`` to the generate call (it does
not change the params).
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
    load_serving_meta, restore_serving_params, save_serving_params,
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
from pytorch_distributed_template_tpu.models.quant import (  # noqa: E402
    quantize_params_w8, validate_quant_config,
)
from pytorch_distributed_template_tpu.parallel import (  # noqa: E402
    dist, mesh_from_config,
)


def main(args, config):
    logger = config.get_logger("quantize")
    assert config.resume is not None, "quantization requires a checkpoint (-r)"

    dist.initialize()
    mesh = mesh_from_config(config)
    model = inject_mesh(config.init_obj("arch", MODELS), mesh)
    # Fail the unquantizable combos up front, with the converter's own
    # error text (MoE experts/routers are not quantized; fused_head is a
    # training-loss mode and is stripped from the serving config below).
    validate_quant_config("w8a16", False, getattr(model, "moe_experts", 0))

    meta = load_serving_meta(config.resume)
    if meta is not None and meta.get("quant") == "w8a16":
        # quantize_params_w8 leaves kernel_q trees untouched, so this
        # would silently write a duplicate artifact whose meta CLAIMS a
        # fresh quantization — refuse instead
        raise SystemExit(
            f"{config.resume} is already a w8a16 serving artifact "
            f"(quantized from {meta.get('source', 'unknown')}); "
            "re-quantizing is a no-op that would write a duplicate "
            "artifact — point -r at the original training checkpoint "
            "or merged-LoRA artifact instead"
        )
    if meta is not None:
        # already a params-only artifact (e.g. scripts/merge_lora.py
        # output) — quantize it directly
        if args.ema:
            raise SystemExit(
                f"--ema has no effect on {config.resume}: it is a "
                "params-only serving artifact (the EMA-or-not choice was "
                "baked in when the artifact was produced — re-run its "
                "producer with --ema instead)"
            )
        src = "params"
        template = jax.eval_shape(
            lambda: model.init(jax.random.key(0), model.batch_template(1))
        )["params"]
        params = restore_serving_params(config.resume, template)
    else:
        state, _ = restore_template_state(config, model, mesh)
        src = "ema_params" if args.ema and state.ema_params is not None \
            else "params"
        params = getattr(state, src)
    def _has_quant_leaves(tree):
        if isinstance(tree, dict):
            return any(k == "kernel_q" or _has_quant_leaves(v)
                       for k, v in tree.items())
        return False

    if _has_quant_leaves(params):
        # meta-less belt-and-suspenders for the same refusal above
        raise SystemExit(
            f"{config.resume} already holds int8 kernel_q leaves; "
            "re-quantizing is a no-op — use the original checkpoint"
        )
    qparams = quantize_params_w8(jax.device_get(params))

    out_dir = (
        config.resume.parent / "serving_w8a16"
        if args.output is None else Path(args.output)
    )
    out_dir.mkdir(parents=True, exist_ok=True)

    # Serving config: the trained experiment's config with the arch args
    # switched to the quant model. ConfigParser's resume rediscovery
    # (config.json next to the artifact) then builds the right model for
    # generate.py with no extra flags.
    serving_cfg = copy.deepcopy(config.config)
    arch_args = serving_cfg.setdefault("arch", {}).setdefault("args", {})
    arch_args["quant"] = "w8a16"
    if arch_args.get("fused_head"):
        arch_args["fused_head"] = False  # training-loss mode; decode emits logits
    (out_dir / "config.json").write_text(json.dumps(serving_cfg, indent=2))

    path = save_serving_params(
        out_dir / "model_w8a16", qparams,
        meta={
            "arch": type(model).__name__,
            "quant": "w8a16",
            "source": str(config.resume),
            "source_params": src,
        },
    )
    n_int8 = sum(
        x.size for x in jax.tree.leaves(qparams)
        if str(x.dtype) == "int8"
    )
    n_all = sum(x.size for x in jax.tree.leaves(qparams))
    logger.info(
        "Quantized %s (%s) -> %s: %.1f%% of %d params stored int8",
        config.resume, src, path, 100.0 * n_int8 / max(n_all, 1), n_all,
    )
    print(path)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="Quantize a checkpoint to an int8 serving artifact"
    )
    parser.add_argument("-c", "--config", default=None, type=str,
                        help="Optional config overlay (fine-tune style).")
    parser.add_argument("-r", "--resume", required=True, type=str,
                        help="Trained checkpoint directory to quantize.")
    parser.add_argument("-s", "--save_dir", default=None, type=str)
    parser.add_argument("-o", "--output", default=None, type=str,
                        help="Artifact directory (default: "
                             "<checkpoint_parent>/serving_w8a16).")
    parser.add_argument("--ema", action="store_true",
                        help="Quantize the EMA shadow weights if present.")
    args, config = ConfigParser.from_args(parser, (), training=False)
    main(args, config)
