import os

# Multi-device CPU mesh for all JAX-based tests: 8 virtual devices. The
# device count is read when jax creates its CPU backend, so the flag must
# be in the environment before any test touches a device (and before worker
# processes are spawned, which inherit it).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Persistent XLA compilation cache: on a small CPU host the tier-1 wall
# clock is dominated by jit-compiling the same tiny-model executables
# identically on every run. The cache keys on serialized HLO + compile
# options + jax/XLA version, so hits are exact; a cold run pays a few
# percent for the writes, every later run skips those compiles entirely.
# Set as env vars (not only jax.config) so spawned worker processes
# inherit it. Opt out / redirect with RAY_TPU_TEST_JAX_CACHE_DIR=off|<dir>.
_cache_dir = os.environ.get("RAY_TPU_TEST_JAX_CACHE_DIR", "")
_owns_cache = False
if _cache_dir != "off":
    if _cache_dir:
        # an explicit redirect must win over an ambient JAX_COMPILATION_CACHE_DIR
        # (e.g. a shared cache exported globally in CI)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
        _owns_cache = True
    elif "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            os.path.expanduser("~"), ".cache", "ray_tpu", "jax_test_cache"
        )
        _owns_cache = True
    # retune write floors + eviction cap only for a directory this conftest
    # owns — an inherited JAX_COMPILATION_CACHE_DIR is someone else's cache
    # and must keep its own policy (zeroed floors write every trivial
    # compile; the max size bounds the dir, but would LRU-evict a shared
    # cache down to 256MB)
    if _owns_cache:
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_MAX_SIZE", str(256 * 1024 * 1024)
        )
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    if _cache_dir != "off" and "JAX_COMPILATION_CACHE_DIR" in os.environ:
        # jax reads this variable at import; a plugin that imported jax
        # before this file ran would have missed it
        jax.config.update(
            "jax_compilation_cache_dir",
            os.environ["JAX_COMPILATION_CACHE_DIR"],
        )
    if _owns_cache:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            int(os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]),
        )
        jax.config.update(
            "jax_compilation_cache_max_size",
            int(os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"]),
        )
except ImportError:
    pass

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests excluded from the tier-1 `-m 'not slow'` run",
    )
    config.addinivalue_line(
        "markers",
        "pallas: Pallas kernel tests — tier-1 runs them in interpret mode "
        "on CPU; they must FAIL (never skip) on divergence from the dense "
        "reference, and test_paged_attention.py budgets their wall clock",
    )
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection tests (ray_tpu._private."
        "faults) — they arm RAY_TPU_FAULTS / call faults.arm() and always "
        "disarm in teardown; seed the rand:<p> selector via "
        "RAY_TPU_TEST_FAULT_SEED (default 0) to reproduce a run exactly",
    )


@pytest.fixture
def fresh_compile():
    """Compile in this test, bypassing the persistent cache both ways. For
    executables the cache cannot give back: the 8-device MoE train step
    aborts the interpreter when the installed jaxlib deserializes it
    (XLA:CPU AOT loader; cold compiles are fine — it would fail only on
    warm-cache runs), and a program compiled for a described, absent TPU
    can be written but never read (tests/test_chip_compile.py)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def ray_start_regular():
    """Fixture ladder rung 1 (reference: python/ray/tests/conftest.py:351)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Rung 2: in-process multi-node cluster (cluster_utils.Cluster)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


# Hang guard: one wedged test must FAIL (with the blocked frame in its
# traceback) instead of silently eating the rest of the tier-1 wall-clock
# budget. Known instance: the data-plane exchange can lose a direct task
# submit (ROADMAP carried item — repro: test_repartition_exchange_exact
# standalone on a 2-core host; head state shows every worker idle, N-1 of
# N merge tasks done, the last parked in dep resolution on a get_objects
# request whose reply never arrives), which parks ray_tpu.get() forever.
# SIGALRM interrupts the main thread's wait; pytest reports a normal
# failure and the fixture teardown still reaps the cluster. Tune/disable
# via RAY_TPU_TEST_HANG_TIMEOUT_S (0 = off).
import signal  # noqa: E402
import sys  # noqa: E402

_HANG_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_HANG_TIMEOUT_S", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if _HANG_TIMEOUT_S <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def _on_alarm(signum, frame):
        # serving flight recorder, if this process holds one: the engine's
        # last step-level events print next to the hang-guard traceback
        # (ISSUE 14 — the wedge's timeline, not just its stack). NEVER a
        # fresh import from a signal handler: the hang may be holding an
        # import lock, and the guard must still fire
        try:
            telemetry = sys.modules.get("ray_tpu.serve.telemetry")
            if telemetry is None:
                raise LookupError("serve telemetry never imported here")
            tel = telemetry._TEL
            if tel is not None and tel.recorder is not None and len(tel.recorder):
                tail = tel.recorder.snapshot()[-20:]
                print(
                    f"[hang-guard] last {len(tail)} flight-recorder events:",
                    file=sys.stderr,
                )
                for ev in tail:
                    print(f"[hang-guard]   {ev}", file=sys.stderr)
                tel.flush_events(force=True)
        except Exception:
            pass
        # retry/attempt state of every outstanding plane rid on the
        # driver's head connection: a wedge now names the request it is
        # stuck on AND how many retransmits it has burned. Same
        # no-fresh-imports rule as above.
        try:
            wmod = sys.modules.get("ray_tpu._private.worker")
            gw = getattr(wmod, "global_worker", None)
            if gw is not None:
                # every conn: head + task leases + actor channels — a
                # wedge can park on any of them
                for row in gw.plane_pending_summary():
                    print(f"[hang-guard] outstanding rid: {row}",
                          file=sys.stderr)
        except Exception:
            pass
        raise TimeoutError(
            f"{item.nodeid} exceeded the {_HANG_TIMEOUT_S}s hang guard "
            "(RAY_TPU_TEST_HANG_TIMEOUT_S); the traceback below is where "
            "it was blocked"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(_HANG_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# Hang forensics: RAY_TPU_TEST_DUMP_AFTER=<seconds> dumps every thread's
# stack to stderr and exits — for chasing in-suite hangs that don't
# reproduce standalone.
import faulthandler  # noqa: E402

faulthandler.enable()
_dump_after = os.environ.get("RAY_TPU_TEST_DUMP_AFTER")
if _dump_after:
    faulthandler.dump_traceback_later(int(_dump_after), exit=True)
import signal  # noqa: E402

if hasattr(signal, "SIGUSR1"):
    faulthandler.register(signal.SIGUSR1, all_threads=True)
