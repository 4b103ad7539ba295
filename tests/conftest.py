"""Test configuration: CPU simulation with 8 virtual devices.

Mirrors the reference's localhost-cluster test pattern (SURVEY.md §4): all
tests run on the jax CPU backend with 8 virtual devices so multi-chip
sharding is exercised without TPU hardware.  Must run before jax imports.
"""
import os
import sys

# make the repo importable regardless of pytest's invocation cwd
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _test_watchdog():
    """Per-test hang watchdog: a blocked queue/lock must surface as a
    test FAILURE, not an unbounded suite stall (round-4 postmortem —
    the suite deadlocked at test 50/337 and the snapshot shipped
    unverified).  SIGALRM interrupts lock waits on the main thread, so
    even a bare queue.get() is caught."""
    import signal

    if not hasattr(signal, "SIGALRM"):
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            "test exceeded the 300s hang watchdog (tests/conftest.py)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(300)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope (reference tests use
    new Programs per test via program_guard)."""
    import paddle_tpu as pt
    from paddle_tpu.framework import program as prog_mod
    from paddle_tpu.framework import scope as scope_mod
    from paddle_tpu.framework import unique_name

    old_main = prog_mod._main_program
    old_startup = prog_mod._startup_program
    old_scope = scope_mod._global_scope
    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    scope_mod._global_scope = scope_mod.Scope()
    with unique_name.guard():
        yield
    prog_mod._main_program = old_main
    prog_mod._startup_program = old_startup
    scope_mod._global_scope = old_scope
    # fleet.init installs a global mesh; leaking it into the next test
    # makes plain Executors run SPMD on non-transpiled programs
    from paddle_tpu.distributed.parallel_env import reset_mesh

    reset_mesh()


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


# ---------------------------------------------------------------------------
# Shared mesh fixtures (the XLA_FLAGS 8-virtual-device setup above is THE
# one copy; test files must not re-set it, and mesh construction for tp/dp
# tests lives here instead of per-file duplicates).
# ---------------------------------------------------------------------------


@pytest.fixture
def mesh8():
    """8-device 1D data-parallel mesh installed as the global parallel
    env (what fleet.init would build); torn down after the test."""
    from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                     reset_mesh)

    reset_mesh()
    mesh = init_parallel_env()
    yield mesh
    reset_mesh()


@pytest.fixture
def mesh_dp_mp():
    """2×4 ('dp','mp') mesh for tensor-parallel tests, installed as the
    global parallel env; torn down after the test."""
    from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                     reset_mesh)

    reset_mesh()
    mesh = init_parallel_env(mesh_shape=[2, 4], axis_names=("dp", "mp"))
    yield mesh
    reset_mesh()


@pytest.fixture
def mesh_mp_only():
    """1×8 ('dp','mp') mesh — pure tensor parallelism (dp degree 1)."""
    from paddle_tpu.distributed.parallel_env import (init_parallel_env,
                                                     reset_mesh)

    reset_mesh()
    mesh = init_parallel_env(mesh_shape=[1, 8], axis_names=("dp", "mp"))
    yield mesh
    reset_mesh()
