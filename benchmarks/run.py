#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. It builds the cell's experiment config and
goes through `ConfigParser`, `mesh_from_config`, `MODELS`/`LOADERS`,
`resolve_loss` and `Trainer` as `train.py:main` does, then drives the
trainer's own epoch method (`Trainer._train_epoch`, iteration mode):

set-up   weights made on the device from `--seed` (benchmarks/weights.py)
         and put in the trainer's state, the routers' selection biases
         among them solved first for the seed's own weights and first
         batch (benchmarks/balance.py; only a configuration with
         experts has any); tokens from `--seed`
         (benchmarks/data.py) read back through the program's loader;
         the first steps one at a time, as many as the cell's file
         says under `check.steps` (their losses, the first
         gradient out of AdamW's first moment and the parameters' change
         are the program's side of the output check); the cell's warm-up
         iterations.
window   the same trainer, the same compiled step, for `--seconds`: a
         timer sets the program's local preemption flag, which the loop
         polls every batch, and the harness returns from the epoch
         method before `Trainer.train()` would checkpoint. The window
         closes on `block_until_ready` of the state.
check    after the window: the peak memory is read, the trainer's state
         is freed, and the plain reference (benchmarks/reference/)
         follows the same steps in float32; each number compared is
         printed beside its limit, on both outputs and under `checks`
         in the result's line.

`--trace 1` arms `trainer.trace.request(k)` a third of the way into the
window and reduces the newest `.xplane.pb` (benchmarks/trace_reduce.py)
to the cell's per-layer metrics.

`--rehearse` runs the same code on the CPU at the tiny sizes the cell's
files give under `rehearse`; its line names `platform: "cpu"` and its
exit code is never 0.

Everything but the harness's own lines goes to standard error; the last
line of standard output is the result (benchmarks/lastline.py).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()       # process start, as near as Python gets

import argparse                 # noqa: E402
import copy                     # noqa: E402
import dataclasses              # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import threading                # noqa: E402
import traceback                # noqa: E402
from pathlib import Path        # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import flops, lastline, trace_reduce  # noqa: E402
from benchmarks.stats import quantile                  # noqa: E402

BENCH = ROOT / "benchmarks"
EXIT_REHEARSED = 9


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Facts:
    """What the per-layer reducers read (benchmarks/reducers/)."""
    sizes: dict
    peak: dict
    setup_records: list
    window_records: list
    compile_events: list
    trace: object = None        # trace_reduce.Trace of the capture
    capture: object = None      # the .xplane.pb it was read from


# -- the cell's files -------------------------------------------------------


def load_cell(name: str, rehearse: bool = False) -> tuple:
    """(benchmark, cell, config) with the rehearsal's tiny sizes laid
    over them when asked."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if name not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    cell = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    config = json.loads(
        (BENCH / "configs" / f"{cell['config']}.json").read_text())
    if rehearse:
        tiny = config["rehearse"]
        config["sizes"].update(tiny["sizes"])
        cell["overrides"] = {**cell["overrides"], **tiny["overrides"]}
        cell["data"].update(cell["rehearse"]["data"])
        cell.update({k: v for k, v in cell["rehearse"].items()
                     if k != "data"})
    return bench, cell, config


def experiment_of(cell: dict, config: dict) -> dict:
    """The configuration's experiment with the cell's overrides
    (`;`-separated keychains, as the program's `--set`) laid over it."""
    exp = copy.deepcopy(config["experiment"])
    for keychain, value in cell["overrides"].items():
        *parents, last = keychain.split(";")
        node = exp
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = value
    return exp


def expected_metrics(bench: dict, cell_name: str, trace: bool) -> dict:
    """{name: unit} of the metrics this cell reports in this mode."""
    group = bench["per_layer" if trace else "end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if cell_name in m.get("workloads", [cell_name])}


def layer_values(bench: dict, cell_name: str, facts, say) -> dict:
    """{name: number} of the cell's per-layer metrics whose reader found
    something to read in this run. A reader that finds nothing returns
    None, and its metric is left out of the line: never a 0, and no
    failed run here. Whether a line may lack it is the driver's to say:
    it wants a metric with no `workloads` list in every cell's line."""
    from benchmarks import reducers

    values = {}
    for name in expected_metrics(bench, cell_name, trace=True):
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        value = reducers.get(spec["reducer"])(facts, **spec["args"])
        if value is None:
            say(f"nothing to read for {name}: left out of the line")
        else:
            values[name] = value
    return values


def reference_module(config: dict):
    import importlib

    path = Path(config["reference"])
    return importlib.import_module(
        ".".join(path.with_suffix("").parts))


def optimizer_settings(exp: dict) -> dict:
    """What the reference needs of the experiment: numbers from the
    configuration file, never an object of the program."""
    opt, sched = exp["optimizer"]["args"], exp["lr_scheduler"]
    if sched["type"] != "WarmupCosine" or sched.get("unit") != "step":
        raise ValueError("the reference follows WarmupCosine by step only")
    return {
        "lr": opt["lr"], "betas": opt["betas"], "eps": opt["eps"],
        "weight_decay": opt["weight_decay"],
        "no_decay": opt["weight_decay_exclude"],
        "warmup_steps": sched["args"]["warmup_epochs"],
        "total_steps": sched["args"]["total_epochs"],
        "grad_clip_norm": exp["trainer"]["grad_clip_norm"],
    }


# -- the program under test --------------------------------------------------


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


class Bench:
    """One trainer, built once, driven through set-up and the window."""

    def __init__(self, cell: dict, config: dict, seed: int, run_dir: Path,
                 rehearse: bool, phase=lambda name: None,
                 say=lambda text: None):
        import jax
        import numpy as np

        from benchmarks.data import make_tokens
        from benchmarks.weights import Weights
        from pytorch_distributed_template_tpu import data, models  # noqa: F401
        from pytorch_distributed_template_tpu.config import (
            ConfigParser, LOADERS, METRICS, MODELS,
        )
        from pytorch_distributed_template_tpu.engine import Trainer
        from pytorch_distributed_template_tpu.engine.losses import resolve_loss
        from pytorch_distributed_template_tpu.parallel import mesh_from_config
        from pytorch_distributed_template_tpu.utils.compile_cache import (
            configure_compile_cache,
        )

        self.cell, self.config, self.seed = cell, config, int(seed)
        self.phase, self.say = phase, say
        self.sizes = config["sizes"]
        devices = jax.devices()
        if not rehearse and (devices[0].platform != "tpu"
                             or len(devices) < cell["chips"]):
            raise NoChip(f"this cell needs {cell['chips']} TPU chip(s); jax "
                         f"sees {len(devices)} x {devices[0].platform}")
        self.devices = devices[:cell["chips"]]
        phase("imports, devices")

        exp = experiment_of(cell, config)
        d = cell["data"]
        self.tokens = make_tokens(self.seed, d["rows"], d["seq_len"],
                                  self.sizes["vocab_size"], d["skew"])
        (run_dir / "data").mkdir(parents=True)
        np.save(run_dir / "data" / "train_tokens.npy", self.tokens)
        exp["train_loader"]["args"].update(
            data_dir=str(run_dir / "data"),
            files={"tokens": "train_tokens.npy"})
        exp["trainer"]["save_dir"] = str(run_dir / "runs")
        self.exp = exp
        self.batch_size = exp["train_loader"]["args"]["batch_size"]
        self.tokens_per_step = self.batch_size * d["seq_len"]

        cfg = ConfigParser(exp, run_id="run")
        configure_compile_cache(cfg)
        mesh = mesh_from_config(cfg, devices=self.devices)
        # seed 0 whatever --seed is: the program traces its seed into its
        # init program as a constant, so another seed would compile it
        # anew, and the weights it makes are replaced just below
        self.trainer = Trainer(
            cfg.init_obj("arch", MODELS), resolve_loss(cfg["loss"]),
            [METRICS.get(m) for m in cfg["metrics"]], config=cfg,
            train_loader=cfg.init_obj("train_loader", LOADERS),
            valid_loader=None, mesh=mesh, seed=0, len_epoch=1)
        self.profile_dir = Path(cfg.log_dir) / "profile"
        phase("tokens, config, the program's Trainer")

        ref = reference_module(config)
        self.weights = Weights(ref.param_shapes(self.sizes),
                               ref.init_rules(self.sizes), self.seed)
        self._epoch = 0
        self.fed = 0            # batches the loader has handed over
        self._balancer = None
        self.install_weights()
        phase("weights from the seed into the trainer's state")

    def reseed(self, seed: int) -> None:
        """Another seed's tokens and weights in the same trainer and the
        same compiled step (benchmarks/control.py reads many seeds in one
        process). The loader goes on from the batch it had reached."""
        from benchmarks.data import make_tokens
        from benchmarks.weights import Weights

        d = self.cell["data"]
        self.seed = int(seed)
        self.tokens = make_tokens(self.seed, d["rows"], d["seq_len"],
                                  self.sizes["vocab_size"], d["skew"])
        self.trainer.train_loader.arrays = {"tokens": self.tokens}
        self.weights = Weights(self.weights.shapes, reference_module(
            self.config).init_rules(self.sizes), self.seed)
        self.install_weights()

    def install_weights(self) -> None:
        """The trainer's state at step 0 with the benchmark's weights, in
        one jitted call: params from the seed, fresh optimizer state. The
        program's own initial state is freed first, so that the peak the
        run reports is the program's and not two states side by side."""
        import jax
        import jax.numpy as jnp

        trainer, weights = self.trainer, self.weights
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            trainer.state.params)
        paths = [path_str(p) for p, _ in flat]
        have = {p: tuple(x.shape) for p, (_, x) in zip(paths, flat)}
        if have != weights.shapes:
            odd = sorted(set(have.items()) ^ set(weights.shapes.items()))
            raise ValueError("the program's parameters are not the ones the "
                             f"configuration's reference describes: {odd[:6]}")
        template = trainer.state.replace(params=None, opt_state=None)
        del flat
        self.free()
        self.balance()

        def fresh(template, root, given):
            made = weights.all(root, given)
            params = jax.tree.unflatten(treedef, [made[p] for p in paths])
            return template.replace(step=jnp.zeros((), jnp.int32),
                                    params=params,
                                    opt_state=trainer.tx.init(params))

        trainer.state = jax.jit(
            fresh, out_shardings=trainer.state_sharding)(
                template, weights.root(), weights.given)
        self._paths = paths

    def balance(self) -> None:
        """The routers' selection biases, solved for this seed's weights
        on the batch the next step is fed (benchmarks/balance.py) and
        given to `self.weights`. It runs on an empty chip: the program's
        initial state is gone, the benchmark's not yet made. A
        configuration without such a leaf is passed over."""
        from benchmarks import balance
        from benchmarks.data import batch_rows

        if not balance.bias_paths(self.weights.shapes):
            return
        t0 = time.perf_counter()
        everywhere, place_rows = reference_placement(self.devices)
        if self._balancer is None:
            self._balancer = balance.Balancer(
                reference_module(self.config), self.sizes,
                place_rows=place_rows)
        params = self.weights.make(everywhere)
        solved, report = self._balancer.solve(
            params, batch_rows(self.tokens, self.fed, self.batch_size))
        for leaf in params.values():
            leaf.delete()
        self.weights.give(solved)
        self.say("balance: busiest published expert over the mean, before "
                 "-> after the solve: " + ", ".join(
                     f"{name} {before:.3f} -> {after:.3f}"
                     for name, before, after in report)
                 + f"; {time.perf_counter() - t0:.2f} s")
        self.phase("selection biases solved on the first batch")

    def _params(self) -> dict:
        import jax

        return dict(zip(self._paths, jax.tree.leaves(self.trainer.state.params)))

    def epoch(self, steps: int) -> dict:
        """`steps` iterations through the trainer's own epoch method."""
        self._epoch += 1
        self.fed += int(steps)
        self.trainer.len_epoch = int(steps)
        return self.trainer._train_epoch(self._epoch)

    def first_steps(self, n: int) -> dict:
        """The program's side of the check: n steps, one at a time."""
        import jax

        from benchmarks.reference.common import leaf_norms, sq_sum

        b1 = self.exp["optimizer"]["args"]["betas"][0]
        out = {"losses": []}
        for k in range(n):
            out["losses"].append(float(self.epoch(1)["loss"]))
            if k == 0:
                adam = [s for s in jax.tree.leaves(
                    self.trainer.state.opt_state,
                    is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
                    if hasattr(s, "mu")]
                if len(adam) != 1:
                    raise ValueError("expected one Adam state in the "
                                     f"optimizer state, found {len(adam)}")
                mu = dict(zip(self._paths, jax.tree.leaves(adam[0].mu)))
                out["grad_norms"] = {p: n / (1 - b1)
                                     for p, n in leaf_norms(mu).items()}
        out["update_norms"] = {
            p: math.sqrt(float(sq_sum(
                x - jax.device_put(self.weights.leaf(p), x.sharding))))
            for p, x in self._params().items()}
        return out

    def window(self, seconds: float, trace_steps: int) -> dict:
        import jax

        from pytorch_distributed_template_tpu.utils import preemption

        trainer = self.trainer
        jax.block_until_ready(trainer.state)
        before = len(trainer.recorder.last())
        timers = [threading.Timer(seconds, preemption.set_local)]
        if trace_steps:
            timers.append(threading.Timer(
                seconds / 3, trainer.trace.request, args=(trace_steps,)))
        t_open = time.perf_counter()
        for t in timers:
            t.daemon = True
            t.start()
        try:
            log = self.epoch(10 ** 9)
            jax.block_until_ready(trainer.state)
            t_close = time.perf_counter()
        finally:
            for t in timers:
                t.cancel()
            trainer.trace.close()
            preemption.reset()
        records = [r for r in trainer.recorder.last()[before:]
                   if "wall_ms" in r]
        return {"t_open": t_open, "t_close": t_close, "records": records,
                "log": log}

    def free(self) -> None:
        """Give back the parameters and the optimizer state."""
        import jax

        state = self.trainer.state
        for leaf in jax.tree.leaves((state.params, state.opt_state)):
            if not leaf.is_deleted():
                leaf.delete()

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def reference_placement(devices) -> tuple:
    """Where the benchmark's own programs keep their arrays: `(the
    sharding of a leaf, on every chip; what puts a block of token rows,
    spread over the chips)`."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("rows",))
    return NamedSharding(mesh, P()), lambda x: jax.device_put(
        x, NamedSharding(mesh, P("rows")))


def reference_steps(cell: dict, config: dict, exp: dict, weights, tokens,
                    batch_size: int, devices, mode: str = "f32",
                    first_batch: int = 0) -> dict:
    """The plain reference over the same rows. On several chips its rows
    are spread over them (parameters on every chip, XLA sums the
    gradients), so a four-chip cell's reference takes no longer than a
    one-chip cell's."""
    import jax

    from benchmarks.data import batch_rows
    from benchmarks.reference import common

    check = cell["check"]
    batches = [batch_rows(tokens, first_batch + k, batch_size)
               for k in range(check["steps"])]
    everywhere, place_rows = reference_placement(devices)
    rows = check["rows_per_block"] * len(devices)
    return common.follow_steps(
        reference_module(config), config["sizes"], optimizer_settings(exp),
        weights.make(everywhere), batches, weights,
        mode=mode, rows_per_block=rows, place_rows=place_rows,
        place_leaf=lambda x: jax.device_put(x, everywhere))


def gaps(got: dict, want: dict) -> dict:
    """{name: (number compared, note)} of one side against the reference:
    each step's loss, and by the worst leaf the norms of the first
    gradient and of the parameters' change."""
    from benchmarks.reference.common import worst_leaf_gap

    out = {}
    for k, (g, w) in enumerate(zip(got["losses"], want["losses"]), 1):
        gap = abs(g - w) / abs(w) if math.isfinite(g) else math.inf
        out[f"loss_rel_gap.step{k}"] = (gap, f"program {g!r} reference {w!r}")
    for key in ("grad_norm", "update_norm"):
        gap, leaf = worst_leaf_gap(got[f"{key}s"], want[f"{key}s"])
        out[f"{key}_gap"] = (
            gap, f"worst leaf {leaf}: program {got[f'{key}s'][leaf]!r} "
            f"reference {want[f'{key}s'][leaf]!r}")
    return out


def compare(got: dict, want: dict, limits: dict, say) -> tuple:
    """Each number compared, beside its limit: said as a line, and kept
    as `{name: {value, limit}}` for the result's line (a value that is
    not finite is null there). True when all hold."""
    ok, checks = True, {}
    for name, (value, note) in gaps(got, want).items():
        limit = limits[name.split(".")[0]]
        holds = math.isfinite(value) and value <= limit
        ok &= holds
        checks[name] = {"value": value if math.isfinite(value) else None,
                        "limit": limit}
        say(f"check {name} = {value:.6g} limit {limit:g} "
            f"{'ok' if holds else 'FAIL'} ({note})")
    return ok, checks


# -- one run ----------------------------------------------------------------


def run(args, say) -> tuple:
    """(the result's line, the exit code). A chip run keeps its directory
    (the trace, the program's log) until the cell's next run; a
    rehearsal's carries the process id, since the tests rehearse one cell
    in several processes, and goes however the run ends."""
    bench, cell, config = load_cell(args.workload, args.rehearse)
    run_dir = ROOT / ".cache" / "bench" / cell["name"]
    if args.rehearse:
        run_dir = run_dir.with_name(f"{cell['name']}.rehearsal.{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    try:
        return run_in(run_dir, args, say, bench, cell, config)
    finally:
        if args.rehearse:
            shutil.rmtree(run_dir, ignore_errors=True)


def run_in(run_dir: Path, args, say, bench: dict, cell: dict,
           config: dict) -> tuple:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    t_phase = [time.perf_counter()]

    def phase(name):
        t_phase.append(time.perf_counter())
        say(f"set-up: {name} {t_phase[-1] - t_phase[-2]:.1f} s "
            f"(at {t_phase[-1] - _T0:.1f} s)")

    phase("imports and the cell's files")
    b = Bench(cell, config, args.seed, run_dir, args.rehearse, phase, say)
    kind = b.devices[0].device_kind
    if kind in peaks:
        peak = peaks[kind]
    elif args.rehearse:         # the line's shape, not a utilization
        peak = next(iter(peaks.values()))
    else:
        raise NoChip(f"benchmarks/peaks.json has no device {kind!r}")

    from pytorch_distributed_template_tpu.observability.telemetry import (
        drain_compile_events,
    )

    before_setup = len(b.trainer.recorder.last())
    program = b.first_steps(cell["check"]["steps"])
    phase(f"first {cell['check']['steps']} steps and their readings")
    b.epoch(cell["warmup_iterations"])
    phase(f"{cell['warmup_iterations']} warm-up iterations")
    setup_records = [r for r in b.trainer.recorder.last()[before_setup:]
                     if "wall_ms" in r]
    w = b.window(args.seconds, cell["trace_steps"] if args.trace else 0)
    setup_s = w["t_open"] - _T0
    window_s = w["t_close"] - w["t_open"]
    records = w["records"]
    memory_peak = b.memory_peak_bytes()

    walls = [r["wall_ms"] for r in records]
    steps = len(records)
    logged = [r for r in records if "lr" in r]
    bad = sum(1 for r in logged if not isinstance(r.get("loss"), float))
    skipped = round(float(w["log"].get("skipped", 0.0)) / b.batch_size)
    failed = max(bad, skipped)
    say(f"window {window_s:.3f} s, {steps} steps of {b.tokens_per_step} "
        f"tokens, {len(logged)} losses logged, {failed} failed; "
        f"iteration ms: {[round(x, 1) for x in walls[:400]]}")
    say(f"set-up iteration ms: "
        f"{[round(r['wall_ms'], 1) for r in setup_records]}")
    if steps < 1:
        raise RuntimeError("the window finished no step")
    inside = [e["event"] for r in records for e in r.get("compile_events", ())]
    say(f"compile events inside the window: {len(inside)} {inside[:4]}")

    events = [e for r in b.trainer.recorder.last()
              for e in r.get("compile_events", ())] + drain_compile_events()
    facts = Facts(sizes=b.sizes, peak=peak, setup_records=setup_records,
                  window_records=records, compile_events=events)

    tokens_per_s = steps * b.tokens_per_step / window_s
    values = {"tokens_per_s": tokens_per_s, "step_ms_p90": quantile(walls, 0.9),
              "setup_s": setup_s}
    per_token = flops.model_flops_per_token(
        reference_module(config), b.sizes, cell["data"]["seq_len"])
    values["mfu_pct"] = 100.0 * tokens_per_s * per_token / (
        cell["chips"] * peak["bf16_flops_per_s"])
    device = {"platform": b.devices[0].platform, "kind": kind,
              "count": len(b.devices), "memory_peak_bytes": memory_peak}
    if args.rehearse and not memory_peak:
        device["memory_peak_bytes"] = 1     # the CPU reports none

    breakdown = None
    if args.trace:
        path = trace_reduce.newest_xplane(b.profile_dir)
        say(f"trace {path.relative_to(ROOT)} ({path.stat().st_size} bytes)")
        facts.capture = path
        facts.trace = trace_reduce.Trace(
            trace_reduce.load_xplane(path),
            cell.get("step_module", "train_step"), rehearse=args.rehearse)
        device["busy_s"], device["window_s"] = (facts.trace.busy_s,
                                                facts.trace.window_s)
        say(f"traced {facts.trace.steps} steps on {facts.trace.device} of "
            f"{facts.trace.n_devices} device plane(s)")
        breakdown = facts.trace.breakdown()
        values = layer_values(bench, cell["name"], facts, say)

    b.free()
    t_ref = time.perf_counter()
    want = reference_steps(cell, config, b.exp, b.weights, b.tokens,
                           b.batch_size, b.devices)
    say(f"reference: {cell['check']['steps']} steps in "
        f"{time.perf_counter() - t_ref:.1f} s, after the window")

    def say_on_both(text):      # nothing follows these on standard error
        say(text)
        print(text, file=sys.stderr, flush=True)

    correct, checks = compare(program, want, cell["check"]["limits"],
                              say_on_both)
    correct &= failed == 0 and len(logged) > 0

    expected = expected_metrics(bench, cell["name"], bool(args.trace))
    if args.trace:      # what no reader found is not in the line
        expected = {n: u for n, u in expected.items() if n in values}
    text = lastline.build(
        correct=correct, attempted=steps, failed=failed, values=values,
        expected=expected, device=device, trace=bool(args.trace),
        breakdown=breakdown, checks=checks)
    return text, (EXIT_REHEARSED if args.rehearse else 0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    out = lastline.Stdout()
    try:
        text, code = run(args, out.say)
    except BaseException:       # no result line, whatever went wrong
        traceback.print_exc()
        out.finish(None, 1)
    out.finish(text, code)


if __name__ == "__main__":
    main()
