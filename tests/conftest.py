"""Test harness: force an 8-device virtual CPU platform before JAX import.

Mirrors the reference's testing seam analysis (SURVEY.md §4): pjit sharding
and collectives are exercised host-side on a virtual device mesh
(``--xla_force_host_platform_device_count``) so no TPU slice is needed.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = " --xla_force_host_platform_device_count=8"
# a loaded box can miss XLA:CPU's default 40 s collective-rendezvous
# termination window, which ABORTS the whole pytest process. Slow is
# fine; aborted is not.
_flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
           " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _flags

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
# Numerical tests assume exact f32 matmuls (TPU bf16-MXU defaults would add
# ~1e-3 noise); production code paths keep the fast default.
jax.config.update("jax_default_matmul_precision", "highest")
# Persist compiled executables across test runs, where
# JAX_COMPILATION_CACHE_DIR says or in <checkout>/.jax_cache
# (polyrl_tpu/utils/xla_cache.py).
from polyrl_tpu.utils.xla_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Background-lane thread names that must NEVER survive a completed fit:
# the pipelined trainer's producer (trainer/pipeline.py) and the async
# weight-push round (transfer/interface.py + fake rollouts in tests/bench).
_LANE_THREAD_PREFIXES = ("rollout-pipeline", "weight-push")
# Long-lived NON-daemon pools owned by libraries, kept alive by design:
# concurrent.futures executors (reward managers, senders' notify pools)
# and orbax's per-process checkpoint machinery (metadata_store_*, the
# *_ch_* per-item handler commit threads). Not leaks — excluded from the
# new-non-daemon check (the named lane check above stays unconditional).
def _infra_thread(name: str) -> bool:
    return (name.startswith(("ThreadPoolExecutor", "metadata_store"))
            or "_ch_" in name)


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """Post-test leak guard (quick tier): the pipelined trainer added
    background lanes, and a lane leaking across tests would serialize the
    whole suite behind a stray generation or poison a later fit. Fails the
    test if, after a short drain grace, (a) any named pipeline/push-lane
    thread is still alive, or (b) a NEW non-daemon thread created during
    the test survived it (ThreadPoolExecutor workers excepted — reward
    managers and orbax keep idle non-daemon pools by design)."""
    before = set(threading.enumerate())
    yield
    if request.node.get_closest_marker("quick") is None:
        return

    def leaked() -> list:
        out = []
        for t in threading.enumerate():
            if not t.is_alive() or t is threading.main_thread():
                continue
            if t.name.startswith(_LANE_THREAD_PREFIXES):
                out.append(t)
            elif (t not in before and not t.daemon
                  and not _infra_thread(t.name)):
                out.append(t)
        return out

    stray = leaked()
    deadline = time.monotonic() + 2.0
    while stray and time.monotonic() < deadline:
        time.sleep(0.05)
        stray = leaked()
    assert not stray, (
        "background threads leaked past the test: "
        f"{[(t.name, 'daemon' if t.daemon else 'non-daemon') for t in stray]}")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# -- quick/full test tiers (VERDICT r4 item 8) ------------------------------
# The suite grew past 14 min on this 1-core box (TSAN rebuild, serving
# stress, multi-process fits dominate). `-m quick` is the iteration tier
# (~5 min); the FULL suite stays the pre-commit bar. Every test outside the
# heavy modules is auto-marked quick so new tests land in the fast tier by
# default; a test can opt out with an explicit @pytest.mark.slow.

_HEAVY_MODULES = {
    "test_tsan_and_parallel_aux",   # TSAN manager rebuild + load hammer
    "test_examples",                # 8B recipe end-to-end at true width
    "test_multihost",               # 2- and 4-process jax.distributed fits
    "test_chaos",                   # cascading mid-stream death scenarios
    "test_salvage_chaos",           # manager SIGKILL mid-decode + salvage
    "test_colocated_hybrid",        # time-slice release/resume cycles
    "test_rollout_server",          # serving stress + TTFT under load
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = getattr(item.module, "__name__", "")
        if mod in _HEAVY_MODULES or item.get_closest_marker("slow"):
            continue
        if item.get_closest_marker("quick") is None:
            item.add_marker(pytest.mark.quick)
