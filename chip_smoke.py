#!/usr/bin/env python
"""On-chip smoke test: the trainer's whole path on a TPU, through the
entry points a user would call.

    python chip_smoke.py              # one chip: train -> resume -> eval -> kernel
    python chip_smoke.py --chips 4    # four chips: data-parallel vs one chip, nothing else

The parent process is stdlib-only and NEVER imports jax: a process that
has touched jax holds the chip, and a child that needs it then fails or
hangs. Every phase is one child process, run one after the other:

probe   ``chip_smoke.py --child probe``: the devices jax sees, and the
        chip's peak FLOP/s from observability/profiler's table. Anything
        but a TPU ends the run here — a machine with no chip fails in
        seconds instead of training GPT-2 on the CPU.
train   ``python train.py -c <cfg> --seed 0``: ~20 steps + validation +
        checkpoint of ``configs/gpt2_small.json`` at full width (only
        the epoch/step counts, loader ``n``, ``save_period`` and
        ``save_dir`` are changed).
resume  ``python train.py -r <checkpoint>`` in a NEW process: starts at
        the saved step, and the persistent compile cache serves the
        train step (hits > 0).
eval    ``python test.py -r <checkpoint>``: the loss equals the
        trainer's own validation loss for that checkpoint.
kernel  ``chip_smoke.py --child kernel``: the Pallas flash kernel,
        compiled (``interpret=False``), forward + backward against the
        XLA reference.

Each phase prints one JSON line; the LAST line of stdout is exactly
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as a child that held the chip reported it. Exit code 0 only when every
phase passed on a TPU.

``--rehearse`` is for a CPU dry run of the control flow (tiny
``--config`` from outside, kernel in interpret mode at a small shape):
it keeps going past the platform check and can never end in ok.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent
TOTAL_BUDGET_S = 1100           # the driver allows 1200 s
KERNEL_SHAPE = (8, 1024, 12, 64)            # GPT-2-small attention
STEPS = 20
ONE_CHIP_ENV = {                # libtpu's own way to show a process one chip
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# children that need jax (run as ``chip_smoke.py --child NAME``)
# ---------------------------------------------------------------------------


def child_probe(config_path):
    import jax

    from pytorch_distributed_template_tpu import models  # noqa: F401
    from pytorch_distributed_template_tpu.config import MODELS
    from pytorch_distributed_template_tpu.observability.profiler import (
        peak_flops_per_device,
    )
    from pytorch_distributed_template_tpu.parallel.dist import device_summary

    arch = json.loads(Path(config_path).read_text())["arch"]
    model = MODELS.get(arch["type"])(**arch.get("args", {}))
    print(json.dumps({
        "device": device_summary(),
        "peak_flops": peak_flops_per_device(jax.devices()[0]),
        "model": {k: int(getattr(model, k))
                  for k in ("n_layer", "d_model", "vocab_size")},
    }))


def child_kernel(shape, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_template_tpu.ops.attention import (
        multihead_attention,
    )
    from pytorch_distributed_template_tpu.ops.flash import flash_attention
    from pytorch_distributed_template_tpu.parallel.dist import device_summary

    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    ref = loss(lambda q, k, v: multihead_attention(q, k, v, causal=True))

    t0 = time.perf_counter()
    lowered = flash.lower(q, k, v)
    custom_calls = lowered.as_text().count("tpu_custom_call")
    (_, out_f), grads_f = jax.block_until_ready(flash(q, k, v))
    compile_s = time.perf_counter() - t0
    (_, out_r), grads_r = jax.block_until_ready(ref(q, k, v))

    def rel_err(a, b):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-9))

    errs = {"out": rel_err(out_f, out_r)}
    for name, gf, gr in zip(("dq", "dk", "dv"), grads_f, grads_r):
        errs[name] = rel_err(gf, gr)
    finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                 for x in (out_f, *grads_f))
    print(json.dumps({
        "device": device_summary(), "shape": list(shape),
        "interpret": interpret, "tpu_custom_call": custom_calls,
        "compile_and_first_run_s": round(compile_s, 2),
        "max_rel_err": errs, "finite": finite,
    }))


# ---------------------------------------------------------------------------
# the stdlib-only parent
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.out = Path(args.out).resolve()
        self.runs = HERE / ".cache" / "chip_smoke"   # checkpoints: GBs
        self.deadline = time.monotonic() + TOTAL_BUDGET_S
        self.device = None       # as reported by a child, never assumed
        self.peak_flops = None
        self.model = None
        shutil.rmtree(self.out, ignore_errors=True)
        shutil.rmtree(self.runs, ignore_errors=True)
        self.out.mkdir(parents=True)
        self.runs.mkdir(parents=True)

    # -- plumbing -----------------------------------------------------------

    def emit(self, line: dict) -> None:
        print(json.dumps(line), flush=True)
        with open(self.out / "phases.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")

    def run(self, name, cmd, env_extra=None, cap_s=700):
        """Run one child to its end; its output goes to <out>/<name>.log.
        The child gets its own process group so a timeout stops
        everything it started."""
        env = dict(os.environ)
        env.update(env_extra or {})
        timeout = min(cap_s, self.deadline - time.monotonic())
        check(timeout > 5, f"{name}: no time left in the {TOTAL_BUDGET_S}s budget")
        log = self.out / f"{name}.log"
        t0 = time.monotonic()
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, *map(str, cmd)], cwd=HERE, env=env,
                stdout=f, stderr=subprocess.STDOUT, start_new_session=True)
            try:
                rc = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                try:    # whatever the child left behind goes with it
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        secs = round(time.monotonic() - t0, 1)
        text = log.read_text(errors="replace")
        if rc != 0:
            sys.stderr.write(f"--- {name}: tail of {log} ---\n"
                             + text[-6000:] + "\n")
        check(rc is not None, f"{name}: timed out after {timeout:.0f}s")
        check(rc == 0, f"{name}: exit code {rc}")
        return text, secs

    def child_json(self, name, child_args, **kw):
        text, secs = self.run(
            name, [Path(__file__).resolve(), "--child", *child_args], **kw)
        lines = [ln for ln in text.splitlines() if ln.startswith("{")]
        check(lines, f"{name}: child printed no result")
        return json.loads(lines[-1]), secs

    def check_device(self, device, count):
        if self.device is None:
            self.device = device
        if self.args.rehearse and device["platform"] != "tpu":
            return
        check(device["platform"] == "tpu",
              f"ran on {device['platform']!r}, not on a TPU")
        check(device["count"] == count,
              f"{device['count']} devices visible, this run needs {count}")

    def derive_config(self, name, global_batch=None):
        cfg = json.loads(Path(self.args.config).read_text())
        tl, vl = cfg["train_loader"]["args"], cfg["valid_loader"]["args"]
        if global_batch:
            tl["batch_size"] = vl["batch_size"] = global_batch
        tl["n"] = tl["batch_size"] * STEPS
        vl["n"] = vl["batch_size"] * 4
        cfg["trainer"].update(epochs=1, len_epoch=STEPS, save_period=1,
                              save_dir=str(self.runs / name))
        path = self.out / f"{name}.json"
        path.write_text(json.dumps(cfg, indent=2))
        return path, cfg

    # -- reading a finished run ----------------------------------------------

    def read_run(self, save_dir, cfg, newest_of=1):
        """What one train.py run left in its run dir, reduced to the
        numbers this script reports and checks."""
        runs = sorted((Path(save_dir) / cfg["name"] / "train").iterdir(),
                      key=lambda p: p.stat().st_mtime)
        check(len(runs) == newest_of, f"expected {newest_of} run dirs under "
              f"{save_dir}, found {len(runs)}")
        run = runs[-1]
        summary = json.loads((run / "summary.json").read_text())
        records = sorted(
            (json.loads(ln) for ln in
             (run / "telemetry.jsonl").read_text().splitlines() if ln),
            key=lambda r: r["step"])
        steps = [r for r in records if "wall_ms" in r]
        losses = [r["loss"] for r in steps if "loss" in r]
        # the recorder nulls a non-finite float, so None counts as one
        logged = [r for r in steps if "lr" in r]
        check(logged and len(losses) == len(logged)
              and all(isinstance(x, float) and math.isfinite(x)
                      for x in losses),
              f"non-finite or missing training loss in {run}")
        for key in ("loss", "val_loss"):
            check(math.isfinite(summary.get(key, math.nan)),
                  f"summary {key} is not finite: {summary.get(key)}")
        events = [e for r in records for e in r.get("compile_events", ())]
        batch = cfg["train_loader"]["args"]["batch_size"]
        seq = cfg["train_loader"]["args"]["seq_len"]
        count = summary["device"]["count"]
        ex_s = summary["examples_per_sec"]
        median_wall = statistics.median(r["wall_ms"] for r in steps[1:])
        facts = {
            "run_dir": str(run),
            "device": summary["device"],
            "steps": len(steps), "first_step": steps[0]["step"],
            "first_loss": losses[0], "last_loss": losses[-1],
            "losses": losses,
            "val_loss": summary["val_loss"],
            "steps_per_sec": round(ex_s / batch, 3),
            "tokens_per_sec": round(ex_s * seq, 1),
            "tokens_per_sec_per_chip": round(ex_s * seq / count, 1),
            "first_step_s": round(steps[0]["wall_ms"] / 1e3, 2),
            # host clock per loop iteration. The epoch meter above spans
            # only ~19 steps, and iterations 2-3 still pay one-time host
            # work (seconds on the chip), so the steady rate is read from
            # the median iteration instead: the loop runs at most a step
            # ahead of the device (the health monitor fetches one step
            # deferred), so in steady state an iteration lasts one step
            "step_wall_ms": [round(r["wall_ms"]) for r in steps],
            "median_step_wall_ms": median_wall,
            "median_data_wait_ms": statistics.median(
                r["data_wait_ms"] for r in steps[1:]),
            "steady_steps_per_sec": round(1e3 / median_wall, 3),
            "steady_tokens_per_sec": round(batch * seq * 1e3 / median_wall, 1),
            "compile_s": round(sum(
                e.get("dur_ms", 0) for e in events
                if e["event"].endswith("backend_compile_duration")) / 1e3, 2),
            "cache_hits": sum(e["event"].endswith("cache_hits")
                              for e in events),
            "cache_misses": sum(e["event"].endswith("cache_misses")
                                for e in events),
            "peak_bytes_in_use": max(
                (d.get("peak_bytes_in_use", 0) for r in records
                 for d in r.get("devices", {}).values()), default=None),
            "step_program": next(
                (r["step_program"] for r in records if "step_program" in r),
                None),
        }
        if self.peak_flops and self.model:
            # model FLOPs per token, forward + backward (recomputation
            # not counted): 6 per matmul weight, plus causal attention
            m = self.model
            matmul_params = (12 * m["n_layer"] * m["d_model"] ** 2
                             + m["d_model"] * m["vocab_size"])
            flops_per_token = (6 * matmul_params
                               + 6 * m["n_layer"] * seq * m["d_model"])
            per_flop_s = flops_per_token / (self.peak_flops * count)
            facts["mfu"] = round(per_flop_s * facts["tokens_per_sec"], 4)
            facts["steady_mfu"] = round(
                per_flop_s * facts["steady_tokens_per_sec"], 4)
            facts["mfu_peak_from"] = self.device["kind"]
        return run, facts

    # -- phases ---------------------------------------------------------------

    def phase_probe(self, count):
        res, secs = self.child_json("probe", ["probe", self.args.config],
                                    cap_s=180)
        self.peak_flops, self.model = res["peak_flops"], res["model"]
        self.check_device(res["device"], count)
        self.emit({"phase": "probe", "seconds": secs, **res})

    def phase_train(self):
        cfg_path, cfg = self.derive_config("train")
        _, secs = self.run("train", ["train.py", "-c", cfg_path, "--seed", 0])
        run, facts = self.read_run(self.runs / "train", cfg)
        self.check_device(facts["device"], 1)
        check(facts["steps"] == STEPS and facts["first_step"] == 0,
              f"expected steps 0..{STEPS - 1}, got {facts['steps']} from "
              f"{facts['first_step']}")
        ckpt = run / "checkpoint-epoch1"
        check((ckpt / "_METADATA").exists(), f"no checkpoint at {ckpt}")
        # the loader's row gather is the native batcher (data/native),
        # built with g++ on first use: a silent numpy fallback would
        # mean the tree is not buildable from what git commits
        lib = HERE / ".build" / "libbatcher.so"
        check(lib.exists(), f"native batcher was not built at {lib}")
        self.emit({"phase": "train", "seconds": secs,
                   "native_batcher": "built", **facts})
        return run, cfg, facts

    def phase_resume(self, run, cfg, cold):
        ckpt = run / "checkpoint-epoch1"
        saved = json.loads(
            (run / "checkpoint-epoch1.data_state.json").read_text())
        _, secs = self.run(
            "resume", ["train.py", "-r", ckpt, "--seed", 0,
                       "--set", "trainer;epochs", 2])
        run2, facts = self.read_run(self.runs / "train", cfg, newest_of=2)
        self.check_device(facts["device"], 1)
        check(facts["first_step"] == saved["global_step"] == STEPS,
              f"resumed at step {facts['first_step']}, checkpoint was "
              f"saved at {saved['global_step']}")
        check(facts["cache_hits"] > 0,
              "the persistent compile cache served nothing to the "
              "resumed process")
        self.emit({"phase": "resume", "seconds": secs, **facts,
                   "cold_first_step_s": cold["first_step_s"],
                   "warm_first_step_s": facts["first_step_s"],
                   "cold_compile_s": cold["compile_s"],
                   "warm_compile_s": facts["compile_s"]})
        return run2, facts

    def phase_eval(self, run2, cfg, resumed):
        ckpt = run2 / "checkpoint-epoch2"
        check((ckpt / "_METADATA").exists(), f"no checkpoint at {ckpt}")
        _, secs = self.run("eval", ["test.py", "-r", ckpt])
        test_runs = sorted((self.runs / "train" / cfg["name"] / "test")
                           .iterdir())
        check(len(test_runs) == 1, f"expected one test run: {test_runs}")
        res = json.loads((test_runs[0] / "summary.json").read_text())
        self.check_device(res["device"], 1)
        want = resumed["val_loss"]
        check(math.isfinite(res["loss"])
              and abs(res["loss"] - want) <= 2e-3 * abs(want),
              f"test.py loss {res['loss']} != trainer's validation loss "
              f"{want} for the same checkpoint")
        self.emit({"phase": "eval", "seconds": secs, "loss": res["loss"],
                   "trainer_val_loss": want, "n_samples": res["n_samples"],
                   "device": res["device"]})

    def phase_kernel(self):
        shape = (2, 128, 4, 64) if self.args.rehearse else KERNEL_SHAPE
        res, secs = self.child_json(
            "kernel", ["kernel", ",".join(map(str, shape)),
                       "interpret" if self.args.rehearse else "compiled"],
            cap_s=300)
        self.check_device(res["device"], 1)
        check(res["finite"], "flash attention produced non-finite values")
        if not self.args.rehearse:
            check(res["tpu_custom_call"] > 0,
                  "no tpu_custom_call in the lowered flash kernel")
        worst = max(res["max_rel_err"].values())
        check(worst <= 3e-2, f"flash vs XLA reference: {res['max_rel_err']}")
        self.emit({"phase": "kernel", "seconds": secs, **res})

    def phase_dp(self):
        """Data parallel over four chips against the same job, seed and
        global batch on one chip. The arms run one after the other."""
        arms = {}
        for name, env in (("dp4", None), ("one", ONE_CHIP_ENV)):
            cfg_path, cfg = self.derive_config(name, global_batch=32)
            _, secs = self.run(
                name, ["train.py", "-c", cfg_path, "--seed", 0],
                env_extra=None if self.args.rehearse else env)
            _, facts = self.read_run(self.runs / name, cfg)
            arms[name] = facts
            self.emit({"phase": name, "seconds": secs, **facts})
        dp, one = arms["dp4"], arms["one"]
        self.check_device(dp["device"], 4)
        if not self.args.rehearse:
            check(one["device"]["count"] == 1,
                  f"the one-chip arm saw {one['device']['count']} chips")
        prog = dp["step_program"] or {}
        check(prog.get("batch_devices") == 4 and prog.get("param_devices") == 4,
              f"batch/params are not on four distinct devices: {prog}")
        check(len(dp["losses"]) == len(one["losses"]), "loss curves differ "
              "in length")
        gap = max(abs(a - b) for a, b in zip(dp["losses"], one["losses"]))
        check(gap <= 0.05 and abs(dp["val_loss"] - one["val_loss"]) <= 0.05,
              f"four-chip and one-chip losses disagree: max gap {gap}, "
              f"val {dp['val_loss']} vs {one['val_loss']}")
        self.emit({
            "phase": "dp_compare", "max_loss_gap": gap,
            "val_loss": [dp["val_loss"], one["val_loss"]],
            "tokens_per_sec": [dp["tokens_per_sec"], one["tokens_per_sec"]],
            "tokens_per_sec_per_chip": [dp["tokens_per_sec_per_chip"],
                                        one["tokens_per_sec_per_chip"]],
            "scaling": round(dp["tokens_per_sec"] / one["tokens_per_sec"], 3),
            "steady_tokens_per_sec": [dp["steady_tokens_per_sec"],
                                      one["steady_tokens_per_sec"]],
            "steady_scaling": round(dp["steady_tokens_per_sec"]
                                    / one["steady_tokens_per_sec"], 3),
            "step_program": prog,
        })

    # -- driver ---------------------------------------------------------------

    def main(self) -> int:
        ok = False
        try:
            check((HERE / "train.py").exists() and (HERE / "test.py").exists(),
                  f"{HERE} holds no train.py/test.py: not a checkout")
            if self.args.chips == 4:
                self.phase_probe(4)
                self.phase_dp()
            else:
                self.phase_probe(1)
                run, cfg, cold = self.phase_train()
                run2, resumed = self.phase_resume(run, cfg, cold)
                self.phase_eval(run2, cfg, resumed)
                self.phase_kernel()
            ok = (not self.args.rehearse and self.device is not None
                  and self.device["platform"] == "tpu")
        except Exception as e:  # noqa: BLE001 — every failure ends in
            if not isinstance(e, PhaseFailed):   # "ok": false and rc != 0
                traceback.print_exc()
            self.emit({"phase": "failed", "error": f"{type(e).__name__}: {e}"})
        finally:
            shutil.rmtree(self.runs, ignore_errors=True)   # checkpoints
        print(json.dumps({"ok": ok, "device": self.device}), flush=True)
        return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the data-parallel phase and its one-chip "
                        "comparison arm")
    p.add_argument("--config", default=str(HERE / "configs/gpt2_small.json"),
                   help="base config (a tiny one for a CPU rehearsal)")
    p.add_argument("--out", default=str(HERE / "chiprun_out/chip_smoke"),
                   help="phase logs, derived configs, phases.jsonl")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU dry run of the control flow; never ends in ok")
    p.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.child:
        kind, *rest = args.child
        if kind == "probe":
            child_probe(rest[0])
        elif kind == "kernel":
            child_kernel(tuple(int(x) for x in rest[0].split(",")),
                         rest[1] == "interpret")
        else:
            raise SystemExit(f"unknown child {kind!r}")
        return 0
    return Smoke(args).main()


if __name__ == "__main__":
    sys.exit(main())
