"""bench.py supervisor robustness: a hung phase child must degrade to
partial results — global wall-clock budget, per-phase row emission as rows
complete, best-so-far JSON on SIGTERM — instead of losing the work that
already finished. Partial is not passing: the exit code is non-zero
whenever a phase has no result, and no phase falls back to another device."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
if REPO not in sys.path:  # bench.py lives at the repo root, not in tests/
    sys.path.insert(0, REPO)

# fake bench child: the raw phase answers instantly, every other phase
# sleeps forever (the forced-hang child the supervisor must contain)
FAKE_CHILD = """\
import json, os, sys, time
mode = os.environ.get("RAY_TPU_BENCH_CHILD")
if mode == "raw":
    print(json.dumps({
        "metric": "fake_raw_tokens_per_sec", "value": 123.0,
        "unit": "tokens/s/chip", "mfu": 0.5, "device": "fake",
        "vs_baseline": 1.0,
    }))
    sys.exit(0)
time.sleep(3600)
"""

# same shape, but the hanging child honors the watchdog contract: it
# registers a SIGUSR2 faulthandler on $RAY_TPU_BENCH_STACKDUMP (exactly
# what bench._install_stack_dumper does), so the supervisor can collect
# its thread stacks before the kill
FAKE_CHILD_WITH_DUMPER = """\
import faulthandler, json, os, signal, sys, threading, time
mode = os.environ.get("RAY_TPU_BENCH_CHILD")
if mode == "raw":
    print(json.dumps({
        "metric": "fake_raw_tokens_per_sec", "value": 123.0,
        "unit": "tokens/s/chip", "mfu": 0.5, "device": "fake",
        "vs_baseline": 1.0,
    }))
    sys.exit(0)
path = os.environ.get("RAY_TPU_BENCH_STACKDUMP")
if path:
    faulthandler.register(signal.SIGUSR2, file=open(path, "w"), all_threads=True)
def wedged_collective():
    time.sleep(3600)
t = threading.Thread(target=wedged_collective, name="tpu-collective", daemon=True)
t.start()
time.sleep(3600)
"""


@pytest.fixture
def fake_child(tmp_path):
    p = tmp_path / "fake_bench_child.py"
    p.write_text(FAKE_CHILD)
    return str(p)


def _bench_env(fake_child, results_path, budget_s):
    env = dict(
        os.environ,
        RAY_TPU_BENCH_CHILD_SCRIPT=fake_child,
        RAY_TPU_BENCH_RESULTS=str(results_path),
        RAY_TPU_BENCH_TOTAL_BUDGET_S=str(budget_s),
        RAY_TPU_BENCH_OVERHEAD_REPS="1",
        RAY_TPU_BENCH_TPU_TIMEOUT_S="300",
    )
    env.pop("RAY_TPU_BENCH_CHILD", None)
    return env


def test_run_child_group_kills_hung_child():
    """_run_child contains a child that sleeps forever: rc=None, bounded
    wall time, no orphan left holding the pipes."""
    import bench

    t0 = time.monotonic()
    rc, out, err = bench._run_child(
        [sys.executable, "-c", "import time; time.sleep(3600)"],
        dict(os.environ), timeout=1.5,
    )
    assert rc is None
    assert time.monotonic() - t0 < 30


def test_budget_degrades_to_partial_results(fake_child, tmp_path):
    """With a tiny global budget and a trainer child that hangs forever:
    the raw row lands in the results file the moment it completes, the hung
    phase is contained, later phases are skipped, and the final JSON still
    prints with the raw row instead of nothing (an earlier chip run lost a
    finished row to exactly this) — under a failing exit code, because
    phases are missing."""
    results = tmp_path / "results.jsonl"
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, BENCH], env=_bench_env(fake_child, results, 12),
        capture_output=True, text=True, timeout=120,
    )
    wall = time.monotonic() - t0
    assert proc.returncode == 1, proc.stderr[-800:]
    assert "phases without a result" in proc.stderr
    # no phase is retried on another device
    assert "fallback" not in proc.stderr.lower()
    # bounded: budget 12s + child-reap slack, nowhere near the 600s the
    # hung trainer would have burned per attempt
    assert wall < 90, f"supervisor ran {wall:.0f}s"

    # the completed phase row was emitted incrementally
    rows = [json.loads(ln) for ln in results.read_text().splitlines()]
    assert [r["phase"] for r in rows] == ["raw"]
    assert rows[0]["row"]["metric"] == "fake_raw_tokens_per_sec"

    # final stdout JSON: best-so-far, raw as primary, trainer flagged
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["metric"] == "fake_raw_tokens_per_sec"
    assert final.get("trainer_row_missing") is True
    assert "budget exhausted" in proc.stderr


def test_hung_phase_dumps_child_thread_stacks(tmp_path):
    """Trainer-phase watchdog: before the supervisor
    group-kills a hung trainer child, SIGUSR2 makes the child's
    faulthandler dump EVERY thread stack, and the dump lands in the
    results file as a phase row — the hang site survives the kill."""
    fake = tmp_path / "fake_child_dumper.py"
    fake.write_text(FAKE_CHILD_WITH_DUMPER)
    results = tmp_path / "results.jsonl"
    proc = subprocess.run(
        [sys.executable, BENCH], env=_bench_env(str(fake), results, 14),
        capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 1, proc.stderr[-800:]

    rows = [json.loads(ln) for ln in results.read_text().splitlines()]
    hung = [r for r in rows if r["row"].get("hung")]
    assert hung, f"no hung row emitted; rows={[r['phase'] for r in rows]}"
    dump = hung[0]["row"]["stack_dump"]
    # faulthandler format: every thread, innermost frame first (thread ids,
    # not names) — the wedged helper thread's hang site must be visible
    # alongside the main thread
    assert "wedged_collective" in dump, dump
    assert "Current thread" in dump and "Thread" in dump, dump
    # the completed raw row still precedes it and the final JSON still prints
    assert rows[0]["phase"] == "raw" and not rows[0]["row"].get("hung")
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["metric"] == "fake_raw_tokens_per_sec"


def test_run_child_stack_dump_collects_before_kill(tmp_path):
    """_run_child unit: SIGUSR2-then-kill collects the dump from a child
    that registered the handler; a child that did not just dies (empty
    dump, no error)."""
    import bench

    dump = tmp_path / "stacks.txt"
    child = tmp_path / "child.py"
    child.write_text(
        "import faulthandler, os, signal, time\n"
        "faulthandler.register(signal.SIGUSR2, "
        "file=open(os.environ['RAY_TPU_BENCH_STACKDUMP'], 'w'), "
        "all_threads=True)\n"
        "time.sleep(3600)\n"
    )
    env = dict(os.environ, RAY_TPU_BENCH_STACKDUMP=str(dump))
    rc, out, err = bench._run_child(
        [sys.executable, str(child)], env, timeout=2.0,
        stack_dump_path=str(dump),
    )
    assert rc is None
    # faulthandler frame format: File "<path>", line N in <func>
    assert "child.py" in dump.read_text()

    dump2 = tmp_path / "stacks2.txt"
    dump2.write_text("")
    rc, out, err = bench._run_child(
        [sys.executable, "-c", "import time; time.sleep(3600)"],
        dict(os.environ), timeout=1.5, stack_dump_path=str(dump2),
    )
    assert rc is None
    assert dump2.read_text() == ""


def test_sigterm_emits_best_so_far(fake_child, tmp_path):
    """SIGTERM mid-hung-phase: the supervisor kills the child group and
    prints the best-so-far JSON instead of dying silently."""
    results = tmp_path / "results.jsonl"
    proc = subprocess.Popen(
        [sys.executable, BENCH], env=_bench_env(fake_child, results, 0),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # wait for the raw row to land (trainer is then hanging)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if results.exists() and results.read_text().strip():
                break
            time.sleep(0.2)
        else:
            raise AssertionError("raw row never landed")
        time.sleep(1.0)  # supervisor is now inside the hung trainer phase
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=10)
    assert proc.returncode == 1, err[-800:]
    final = json.loads(out.strip().splitlines()[-1])
    assert final["metric"] == "fake_raw_tokens_per_sec"
    assert "best-so-far" in err


def test_every_phase_answering_exits_zero(tmp_path):
    """The other side of the exit-code contract: when every phase lands a
    row the supervisor exits 0 and the satellite rows ride the headline."""
    fake = tmp_path / "fake_child_all.py"
    fake.write_text(
        "import json, os\n"
        "mode = os.environ['RAY_TPU_BENCH_CHILD']\n"
        "print(json.dumps({'metric': 'fake_' + mode, 'value': 1.0, "
        "'mfu': 0.5, 'device': 'fake'}))\n"
    )
    results = tmp_path / "results.jsonl"
    proc = subprocess.run(
        [sys.executable, BENCH], env=_bench_env(str(fake), results, 60),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["metric"] == "fake_trainer"
    assert {"raw", "hbm", "rl", "decode"} <= set(final)
    phases = [json.loads(ln)["phase"] for ln in results.read_text().splitlines()]
    assert phases == ["raw", "trainer", "decode", "hbm", "rl"]


def test_unknown_device_kind_is_an_error():
    """The peaks table has no default: a device that is not in it — the CPU
    included — cannot be given an MFU."""
    import bench

    assert bench._peak_flops_kind("TPU v5 lite") == 197e12
    assert bench._peak_flops_kind("TPU v5p") == 459e12
    for kind in ("cpu", "TPU v9"):
        with pytest.raises(SystemExit, match="no bf16 peak"):
            bench._peak_flops_kind(kind)
