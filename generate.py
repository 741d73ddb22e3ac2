"""Sampling CLI: generate continuations from a trained LM checkpoint.

Completes the workflow the reference stops short of (its ``test.py`` is
batch evaluation only, /root/reference/test.py:64-101): train with
``train.py``, evaluate with ``test.py``, sample with this. KV-cached
incremental decoding (engine/generate.py) with greedy / temperature /
top-k / top-p control.

Prompts: ``--prompt`` treats the text as UTF-8 BYTES when the model's
vocab fits bytes (the ByteLMLoader convention, vocab <= 256) and prints
the generated bytes back as text; otherwise pass ``--prompt-ids`` as
comma-separated token ids and ids are printed.

Usage:
    python generate.py -r saved/<name>/train/<run>/model_best \
        --prompt "The meaning of life" --max-new-tokens 128 \
        --temperature 0.8 --top-p 0.95
"""
from __future__ import annotations

import argparse

from pytorch_distributed_template_tpu.config import ConfigParser
import pytorch_distributed_template_tpu.data  # noqa: F401 (registries)
import pytorch_distributed_template_tpu.engine  # noqa: F401
import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.engine.serving import (
    GenerationService,
)
from pytorch_distributed_template_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(args, config):
    logger = config.get_logger("generate")
    configure_compile_cache(config)
    # checkpoint-or-artifact restore + encode/dispatch/decode, shared
    # with the HTTP server (engine/serving.GenerationService); a
    # serving.prefix_cache config block attaches the paged KV pool, so
    # repeated prompts sharing a prefix (few-shot preambles) prefill
    # only their suffix (engine/kvcache.py)
    service = GenerationService(
        config, use_ema=args.ema, tensor_parallel=args.tp,
        prefix_cache=(config.get("serving") or {}).get("prefix_cache"))
    prompt_ids = (
        [int(x) for x in args.prompt_ids.split(",")]
        if args.prompt_ids else None
    )
    # --stop is ALWAYS a string, --stop-id ALWAYS a token id: guessing
    # from the spelling would make the digit characters '0'-'9'
    # impossible to pass as text stops on byte-vocab models
    stop = None
    if args.stop or args.stop_id:
        stop = list(args.stop or []) + [int(i) for i in
                                        (args.stop_id or [])]
    try:
        resp = service.generate(
            prompt=args.prompt, prompt_ids=prompt_ids,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed,
            speculative=args.speculative, stop=stop,
        )
    except ValueError as e:
        raise SystemExit(str(e))
    if "speculative" in resp:
        logger.info("speculative: %s", resp["speculative"])
    if stop is not None:
        logger.info("stop_reason: %s", resp.get("stop_reason"))
    # text in -> text out; ids in -> ids out (a tokenizer may be loaded
    # even for --prompt-ids runs — don't surprise id-mode callers)
    if args.prompt is not None:
        logger.info("prompt   : %s", args.prompt)
        logger.info("generated: %s", resp["text"])
        print(args.prompt + resp["text"])
    else:
        print(",".join(str(t) for t in resp["ids"]))


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="LM sampling CLI")
    parser.add_argument("-c", "--config", default=None, type=str,
                        help="Optional config overlay (fine-tune style).")
    parser.add_argument("-r", "--resume", required=True, type=str,
                        help="Checkpoint directory to sample from.")
    parser.add_argument("-s", "--save_dir", default=None, type=str)
    prompt_group = parser.add_mutually_exclusive_group()
    prompt_group.add_argument("--prompt", default=None, type=str,
                              help="UTF-8 text prompt (byte-vocab models).")
    prompt_group.add_argument("--prompt-ids", default=None, type=str,
                              help="Comma-separated token ids.")
    parser.add_argument("--max-new-tokens", default=64, type=int)
    parser.add_argument("--temperature", default=0.0, type=float,
                        help="<= 0 means greedy.")
    parser.add_argument("--top-k", default=0, type=int)
    parser.add_argument("--top-p", default=0.0, type=float)
    parser.add_argument("--ema", action="store_true",
                        help="Sample the EMA shadow weights if present.")
    parser.add_argument("--speculative", default=0, type=int, metavar="D",
                        help="Greedy prompt-lookup speculative decoding "
                             "with D drafted tokens per model call "
                             "(exact same output, fewer model calls).")
    parser.add_argument("--stop", action="append", default=None,
                        metavar="STR",
                        help="Stop STRING (must encode to one token); "
                             "repeatable. Generation ends when it is "
                             "emitted (the stop token is not printed).")
    parser.add_argument("--stop-id", action="append", default=None,
                        metavar="ID",
                        help="Stop token ID (integer); repeatable.")
    parser.add_argument("--seed", default=0, type=int)
    parser.add_argument("--tp", default=0, type=int,
                        help="Tensor-parallel degree (0 = config "
                             "serving.tensor_parallel, default single "
                             "chip); shards weights + KV over a "
                             "'tensor' mesh axis — greedy output is "
                             "token-identical to tp=1.")
    args, config = ConfigParser.from_args(parser, (), training=False)
    main(args, config)
