"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so sharding tests run hermetically
without TPU hardware (the driver's dryrun does the same). ``XLA_FLAGS`` must be
set before the CPU client is created (env is read at backend-init time, which
pytest's conftest-first import order guarantees); LLVM optimisation is off for
the CPU programs, and modules that take minutes start first (below). The
persistent compile cache is off: sessions point it at ``<repo>/.jax_cache``,
and CPU test programs have no business there.
"""

import collections
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# Most of the suite's time is compiling programs (interpret-mode Pallas
# kernels, unrolled AES circuits) that then run once on tiny shapes; LLVM's
# optimisation passes cost more there than they save.
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_level_kernel_selfcheck():
    """Snapshot/restore the one-time kernel self-check flag around
    every test (dispatch code sets it, not only tests)."""
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    saved = dep._LEVEL_KERNEL_VERIFIED
    yield
    dep._LEVEL_KERNEL_VERIFIED = saved


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables between test modules.

    The suite compiles hundreds of XLA programs in one process; letting
    them accumulate has produced a segfault inside XLA:CPU's compiler
    late in the run (observed 2026-07-30 at the ~240th test, always the
    same shard_map compile, never reproducible in any file subset).
    Per-module cache clearing keeps within-module reuse (where nearly all
    the hits are) while bounding process-lifetime compiler state.
    """
    yield
    jax.clear_caches()


# Modules that take minutes each on the CPU (interpret-mode Pallas kernels,
# 8-device shard_map programs), longest first. Under `--dist loadfile` a
# module runs whole on one worker, and xdist hands modules out in the
# order given here; its own order (most tests first) starts these last, so
# one worker ran on alone for minutes after the others had finished. The
# other modules follow, most tests first.
_LONGEST_MODULES = (
    "test_expand_pallas_fused.py",
    "test_expand_pallas.py",
    "test_eval_paths_planes.py",
    "test_mesh_serving.py",
    "test_streaming_pir.py",
    "test_sharded.py",
    "test_dense_eval_planes_v2.py",
    "test_dpf.py",
    "test_pallas_fast.py",
    "test_dense_eval_planes.py",
    "test_pir.py",
    "test_dcf.py",
    "test_incremental_reuse.py",
    "test_full_domain_direct_exit.py",
    "test_core_fast.py",
    "test_chaos.py",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):  # pytest-xdist loaded
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    counts = collections.Counter(item.path.name for item in items)

    def rank(item):
        name = item.path.name
        if name in _LONGEST_MODULES:
            return (0, _LONGEST_MODULES.index(name))
        return (1, -counts[name])

    items.sort(key=rank)  # stable: each module's tests keep their order
