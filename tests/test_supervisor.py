"""Supervisor layer (resilience/supervisor.py + scripts/supervise.py).

Fast tier: pure-function units (exit classification, backoff,
budgets) and end-to-end supervision of FAKE children — tiny
``python -c`` scripts that read ``PDT_ATTEMPT``, so the whole
spawn → classify → backoff → restart → clean loop runs in seconds
without a jax import. The slow tier drives real ``train.py``
children: the subprocess-level golden resume-equivalence run
(kill@step:k + supervisor + telemetry cross-check), mirroring the CI
``chaos-smoke`` job.
"""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from pytorch_distributed_template_tpu.resilience.supervisor import (
    ENV_ATTEMPT, ENV_HEARTBEAT, EXIT_PREEMPTED, Supervisor,
    SupervisorConfig, classify_exit, compute_backoff,
    read_supervisor_stats,
)

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# pure units
# ---------------------------------------------------------------------------


def test_classify_exit():
    assert classify_exit(0) == "clean"
    assert classify_exit(EXIT_PREEMPTED) == "preemption"
    assert classify_exit(-signal.SIGTERM) == "preemption"
    assert classify_exit(1) == "crash"
    assert classify_exit(137) == "crash"
    assert classify_exit(-signal.SIGKILL) == "crash"
    assert classify_exit(-signal.SIGSEGV) == "crash"
    # a hang verdict wins over whatever signal finally killed the child
    assert classify_exit(-signal.SIGKILL, hang=True) == "hang"
    assert classify_exit(0, hang=True) == "hang"


def test_compute_backoff_growth_cap_and_jitter():
    no_jitter = [compute_backoff(n, 2.0, 60.0, 0.0) for n in range(1, 8)]
    assert no_jitter == [2.0, 4.0, 8.0, 16.0, 32.0, 60.0, 60.0]
    assert compute_backoff(3, 0.0, 60.0, 0.5) == 0.0   # base 0 = immediate
    # jitter stretches by at most the fraction, never shrinks
    lo = compute_backoff(2, 2.0, 60.0, 0.25, rand=lambda: 0.0)
    hi = compute_backoff(2, 2.0, 60.0, 0.25, rand=lambda: 1.0)
    assert lo == 4.0 and hi == 5.0


# ---------------------------------------------------------------------------
# fake-child end-to-end (no jax in the children)
# ---------------------------------------------------------------------------


def _fake_child(body: str):
    """argv for a child whose behavior depends on PDT_ATTEMPT."""
    return [sys.executable, "-c",
            "import os, sys, time\n"
            "attempt = int(os.environ.get('PDT_ATTEMPT', '1'))\n"
            + body]


def _cfg(tmp_path, **kw):
    kw.setdefault("restart_delay_s", 0.05)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("events_path", str(tmp_path / "supervisor.jsonl"))
    return SupervisorConfig(**kw)


def _events(cfg):
    return [json.loads(ln) for ln in
            open(cfg.events_path) if ln.strip()]


def test_crash_then_clean(tmp_path):
    cfg = _cfg(tmp_path, max_restarts=3)
    sup = Supervisor(
        _fake_child("sys.exit(3 if attempt == 1 else 0)"), cfg
    )
    assert sup.run() == 0
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["restarts_total"] == 1
    assert stats["last_restart_cause"] == "crash"
    assert stats["attempts"] == 2
    assert stats["clean"] and not stats["gave_up"]
    names = [e["event"] for e in _events(cfg)]
    assert names == ["start", "spawn", "exit", "restart", "spawn",
                     "exit", "clean"]


def test_budget_exhaustion_preserves_exit_code(tmp_path):
    cfg = _cfg(tmp_path, max_restarts=2)
    sup = Supervisor(_fake_child("sys.exit(7)"), cfg)
    assert sup.run() == 7        # the persistent failure code surfaces
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["gave_up"] and not stats["clean"]
    assert stats["restarts_total"] == 2   # budget allows 2 relaunches
    give_up = next(e for e in _events(cfg) if e["event"] == "give_up")
    assert give_up["reason"] == "budget"


def test_preemption_restarts_do_not_burn_budget(tmp_path):
    """EXIT_PREEMPTED children relaunch even with a zero crash budget:
    preemptions are routine fleet events, not bugs."""
    cfg = _cfg(tmp_path, max_restarts=0)
    sup = Supervisor(
        _fake_child(f"sys.exit({EXIT_PREEMPTED} if attempt < 3 else 0)"),
        cfg,
    )
    assert sup.run() == 0
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["restarts_total"] == 2
    assert stats["causes"] == {"preemption": 2}
    assert sup.crash_restarts == 0
    assert stats["clean"]


def test_preemption_churn_never_trips_crash_loop(tmp_path):
    """Back-to-back preemptions must not satisfy the crash-loop
    heuristic — it exists for bugs, not fleet weather."""
    cfg = _cfg(tmp_path, max_restarts=5, crash_loop_max=1,
               crash_loop_window_s=600.0)
    sup = Supervisor(
        _fake_child(f"sys.exit({EXIT_PREEMPTED} if attempt < 4 else 0)"),
        cfg,
    )
    assert sup.run() == 0
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["restarts_total"] == 3 and stats["clean"]
    assert not stats["gave_up"]


def test_stable_runtime_resets_crash_streak(tmp_path):
    """A crash after a long healthy run is a fresh failure, not the
    Nth of a streak: with budget 1, crash -> stable run -> crash ->
    clean must succeed (without the reset the second crash would
    exhaust the budget)."""
    cfg = _cfg(tmp_path, max_restarts=1, stable_runtime_s=0.3)
    body = (
        "if attempt == 1: sys.exit(3)\n"
        "if attempt == 2:\n"
        "    time.sleep(0.5)\n"
        "    sys.exit(3)\n"
        "sys.exit(0)\n"
    )
    sup = Supervisor(_fake_child(body), cfg)
    assert sup.run() == 0
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["restarts_total"] == 2 and stats["clean"]
    assert any(e["event"] == "stable_reset" for e in _events(cfg))


def test_crash_loop_window_gives_up_early(tmp_path):
    cfg = _cfg(tmp_path, max_restarts=100, crash_loop_window_s=60.0,
               crash_loop_max=2)
    sup = Supervisor(_fake_child("sys.exit(1)"), cfg)
    assert sup.run() == 1
    give_up = next(e for e in _events(cfg) if e["event"] == "give_up")
    assert give_up["reason"] == "crash_loop"
    assert read_supervisor_stats(cfg.events_path)["restarts_total"] <= 3


def test_signal_death_maps_to_128_plus(tmp_path):
    cfg = _cfg(tmp_path, max_restarts=0)
    sup = Supervisor(
        _fake_child("import signal\nos.kill(os.getpid(), "
                    "signal.SIGKILL)"), cfg,
    )
    assert sup.run() == 128 + signal.SIGKILL
    assert read_supervisor_stats(
        cfg.events_path)["causes"] == {}  # gave up before any restart


def test_hang_detection_drains_and_restarts(tmp_path):
    """Attempt 1 beats once then wedges; the supervisor must notice the
    stale heartbeat, SIGTERM-drain, classify the hang, and the
    relaunched attempt finishes clean."""
    cfg = _cfg(tmp_path, max_restarts=2, hang_timeout_s=1.0,
               term_grace_s=0.5, poll_s=0.1)
    body = (
        "hb = os.environ['PDT_HEARTBEAT_FILE']\n"
        "if attempt == 1:\n"
        "    open(hb, 'w').write('beat')\n"
        "    time.sleep(60)\n"
        "sys.exit(0)\n"
    )
    sup = Supervisor(_fake_child(body), cfg)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 30  # not the child's 60s sleep
    stats = read_supervisor_stats(cfg.events_path)
    assert stats["restarts_total"] == 1
    assert stats["last_restart_cause"] == "hang"
    assert stats["clean"]
    assert any(e["event"] == "hang" for e in _events(cfg))


def test_child_env_contract(tmp_path):
    """The supervisor exports attempt/heartbeat/events paths — the
    contract the fault plan's attempt gate, the watchdog heartbeat,
    and serve.py's restart counters rely on."""
    out = tmp_path / "env.json"
    cfg = _cfg(tmp_path, max_restarts=0)
    body = (
        "import json\n"
        f"json.dump({{k: os.environ.get(k) for k in"
        f" ('PDT_ATTEMPT', 'PDT_HEARTBEAT_FILE',"
        f" 'PDT_SUPERVISOR_EVENTS')}}, open({str(out)!r}, 'w'))\n"
        "sys.exit(0)\n"
    )
    Supervisor(_fake_child(body), cfg).run()
    env = json.loads(out.read_text())
    assert env["PDT_ATTEMPT"] == "1"
    assert env["PDT_SUPERVISOR_EVENTS"] == str(cfg.events_path)
    assert env["PDT_HEARTBEAT_FILE"] == str(tmp_path / "heartbeat")


def test_watchdog_touches_heartbeat(tmp_path):
    """StepWatchdog.beat() maintains the heartbeat file even with the
    in-process stall monitor disabled (timeout 0) — external hang
    detection must not depend on the internal one."""
    from pytorch_distributed_template_tpu.utils.watchdog import (
        StepWatchdog,
    )

    hb = tmp_path / "hb"
    wd = StepWatchdog(timeout_s=0, heartbeat_path=hb,
                      heartbeat_interval_s=0.0)
    wd.start()
    assert hb.exists()           # alive before the first step
    first = hb.read_text()
    time.sleep(0.01)
    wd.beat()
    assert hb.read_text() != first
    wd.stop()


def test_watchdog_heartbeat_throttle(tmp_path):
    from pytorch_distributed_template_tpu.utils.watchdog import (
        StepWatchdog,
    )

    hb = tmp_path / "hb"
    wd = StepWatchdog(timeout_s=0, heartbeat_path=hb,
                      heartbeat_interval_s=60.0)
    wd.start()
    stamp = hb.read_text()
    for _ in range(5):
        wd.beat()
    assert hb.read_text() == stamp  # throttled: no rewrite inside 60s


def test_supervise_cli_raw_and_env_defaults(tmp_path):
    """scripts/supervise.py end to end in --raw mode, with the legacy
    MAX_RESTARTS/RESTART_DELAY_S env contract."""
    events = tmp_path / "sup.jsonl"
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "supervise.py"),
         "--events-file", str(events), "--jitter", "0", "--raw", "--",
         sys.executable, "-c",
         "import os, sys; "
         "sys.exit(5 if os.environ['PDT_ATTEMPT'] == '1' else 0)"],
        env={**os.environ, "MAX_RESTARTS": "2", "RESTART_DELAY_S": "0.05"},
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    stats = read_supervisor_stats(events)
    assert stats["restarts_total"] == 1 and stats["clean"]
    start = next(e for e in
                 (json.loads(ln) for ln in open(events) if ln.strip())
                 if e["event"] == "start")
    assert start["max_restarts"] == 2
    assert start["restart_delay_s"] == 0.05


# ---------------------------------------------------------------------------
# slow tier: real train.py children (the subprocess golden run)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_supervised_kill_resume_golden(tmp_path):
    """The ISSUE's golden contract at the PROCESS level: train N steps
    uninterrupted vs PDT_FAULTS=kill@step:k under the supervisor; the
    supervised pair must restart exactly once, resume step-accurately,
    and reproduce the uninterrupted run's logged per-step loss
    trajectory (same seed, CPU)."""
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env_base.pop("PDT_FAULTS", None)
    common = [
        "-c", str(REPO / "configs" / "mnist_debug.json"),
        "--no-validate",
        "--set", "trainer;epochs", "2",
        "--set", "trainer;save_period", "1",
        "--set", "trainer;save_interval_steps", "2",
        "--set", "train_loader;args;synthetic_n", "64",
        # divisible by the virtual 8-device mesh the test env forces
        "--set", "train_loader;args;batch_size", "8",
    ]
    # batch 8 -> log_step = 2: every other step logs a loss record
    def losses(save_root):
        out = {}
        for run in sorted(
                Path(save_root).glob("Mnist_LeNet_Debug/train/*")):
            for line in (run / "telemetry.jsonl").open():
                rec = json.loads(line)
                if rec.get("loss") is not None:
                    # later runs overwrite replayed steps
                    out[rec["step"]] = rec["loss"]
        return out

    r = subprocess.run(
        [sys.executable, str(REPO / "train.py"),
         "-s", str(tmp_path / "base")] + common,
        env=env_base, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    base = losses(tmp_path / "base")
    assert base, "uninterrupted run logged no losses"

    events = tmp_path / "supervisor.jsonl"
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "supervise.py"),
         "--max-restarts", "3", "--restart-delay", "0.5", "--jitter",
         "0", "--events-file", str(events),
         "-s", str(tmp_path / "chaos")] + common,
        env={**env_base, "PDT_FAULTS": "kill@step:11"},
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    stats = read_supervisor_stats(events)
    assert stats["restarts_total"] == 1, stats
    assert stats["last_restart_cause"] == "crash"
    assert stats["clean"] and not stats["gave_up"]

    chaos = losses(tmp_path / "chaos")
    assert set(base) <= set(chaos)
    for step, loss in base.items():
        assert chaos[step] == pytest.approx(loss, rel=1e-4), (
            f"step {step}: base {loss} vs recovered {chaos[step]}")
    # step-accurate completion: the final epoch checkpoint of the
    # resumed run lands on the uninterrupted target (2 epochs x 8)
    ds_files = list(Path(tmp_path / "chaos").glob(
        "*/train/*/checkpoint-epoch2.data_state.json"))
    assert ds_files
    ds = json.loads(max(ds_files, key=lambda p: p.stat().st_mtime)
                    .read_text())
    assert ds["global_step"] == 16
