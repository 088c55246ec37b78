"""Test harness: simulate an 8-device TPU mesh on CPU.

The analog of the reference's distributed-without-a-cluster mechanism
(``tests/unit/common.py:89`` DistributedExec): instead of forking processes
per rank, JAX gives us N virtual devices in one process via
``--xla_force_host_platform_device_count`` — every sharding/collective code
path (GSPMD ZeRO, pipeline ppermute, MoE all_to_all) executes for real on the
CPU mesh.
"""

import os
import sys

# Must be set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Persistent compilation cache: the suite is XLA-compile-dominated; re-runs
# hit the cache and finish in roughly half the cold time.  Placement is the
# library's one rule ($JAX_COMPILATION_CACHE_DIR, else the checkout's
# .jax_cache) — see runtime/compile_cache.py.
from deepspeed_tpu.runtime.compile_cache import configure_persistent_cache  # noqa: E402

configure_persistent_cache(min_compile_time_secs=0.5)
assert jax.device_count() == 8, f"expected 8 virtual CPU devices, got {jax.devices()}"

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="run nightly-tier tests marked @pytest.mark.slow")


def pytest_collection_modifyitems(config, items):
    """Skip `slow` tests by default (CI time budget on the 1-core box) —
    unless --run-slow, an explicit -m expression, or a direct node-ID
    invocation asks for them."""
    if config.getoption("--run-slow") or config.option.markexpr:
        return
    # explicitly-named node IDs run even when slow — but only THOSE items,
    # not every slow test swept up by other path arguments in the same run
    explicit = [a for a in config.args if "::" in a]

    def _named(item):
        return any(item.nodeid == a or item.nodeid.startswith(a + "[")
                   or item.nodeid.startswith(a + "::") for a in explicit)

    skip = pytest.mark.skip(
        reason="slow (nightly tier); use --run-slow or -m slow")
    for item in items:
        if "slow" in item.keywords and not _named(item):
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test gets a fresh global topology (the analog of tearing down
    process groups between DistributedTest cases)."""
    from deepspeed_tpu.parallel import topology
    topology.reset_topology()
    yield
    topology.reset_topology()


# a quarter of Linux's default ``vm.max_map_count`` (65,530): one module of
# the serving tests has been seen to add 23,000 maps before its teardown
_MAP_BUDGET = 16384


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """A worker keeps every XLA:CPU executable it ever compiled — three
    memory maps each, eager ``jnp`` operations included: ~19,000 of them
    five sixths of the way through the suite — and once the process passes
    ``vm.max_map_count`` the NEXT compile dies of a segmentation fault
    inside XLA, in whatever test happens to run (the worker is lost, and
    its tests with it).  Between modules, past a quarter of that limit, drop
    JAX's caches: the maps go with them (57,000 → under 1,000, measured),
    and what a later module needs compiles again."""
    yield
    try:
        with open("/proc/self/maps") as f:
            maps = sum(1 for _ in f)
    except OSError:          # no procfs: nothing to count, nothing to do
        return
    if maps > _MAP_BUDGET:
        import gc
        jax.clear_caches()
        gc.collect()


@pytest.fixture
def eight_devices():
    import jax
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs
