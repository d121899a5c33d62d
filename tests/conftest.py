"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax import
(multi-chip sharding tests run on the virtual mesh; see driver's
dryrun_multichip protocol) and reset framework global state between tests."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the dataset layer's auto mode would download real corpora on a
# networked host — tests must be deterministic and offline-equal
# everywhere (parsers are covered separately on generated fixtures)
os.environ.setdefault("PADDLE_TPU_DATASET", "synthetic")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

# tier-1 compiles thousands of small CPU executables: keep them out of
# the checkout's persistent compile cache (paddle_tpu/core/
# compile_cache.py) — the chip tool copies the tree.  Tests of the cache
# itself switch it back on through the `compile_cache` fixture.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """The persistent compile cache, placed from OUTSIDE as an operator
    would (JAX_COMPILATION_CACHE_DIR) into a fresh directory, with JAX's
    write thresholds at zero so the tiny CPU executables of a test are
    persisted too.  Yields the directory."""
    from jax.experimental.compilation_cache import compilation_cache

    d = str(tmp_path / "jax_cache")
    knobs = {"jax_enable_compilation_cache": True,
             "jax_compilation_cache_dir": d,
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    prev = {k: getattr(jax.config, k) for k in knobs}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    for k, v in knobs.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield d
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _fresh_state():
    """Fresh default programs / scope / name counters per test."""
    import paddle_tpu as fluid
    from paddle_tpu.core import executor as executor_mod
    from paddle_tpu.core import framework as fw
    from paddle_tpu.core.resilience import fault_injector
    from paddle_tpu.core.scope import Scope

    old_main = fw.switch_main_program(fluid.Program())
    old_startup = fw.switch_startup_program(fluid.Program())
    fw.reset_unique_names()
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = Scope()
    yield
    # a chaos test that failed mid-run must not leak armed faults into
    # unrelated tests
    fault_injector().clear()
    fw.switch_main_program(old_main)
    fw.switch_startup_program(old_startup)
    executor_mod._global_scope = old_scope


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (book training flows, subprocess "
        "clusters). Fast subset: pytest -m 'not slow' runs in ~1/3 the "
        "wall time (6:22 vs 18:41 measured); CI runs the full suite.")
    config.addinivalue_line(
        "markers",
        "perf: timing-sensitive microbench test (async input pipeline "
        "overlap, recompile-free hot loops). Tier-1-safe — the "
        "assertions use best-of-N walls and measured-step-derived "
        "workloads so they hold on loaded CI hosts. Run just these: "
        "pytest -m perf")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection test (core/resilience FaultInjector "
        "driving socket drops, truncated frames, corrupt snapshots, "
        "killed trainers). Socket-level single-process cases are fast "
        "and run in tier-1; process-kill scenarios are also marked slow. "
        "Run just the chaos suite: pytest tests/test_resilience.py "
        "-m chaos")
