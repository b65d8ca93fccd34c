"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so sharding tests run hermetically
without TPU hardware (the driver's dryrun does the same). ``XLA_FLAGS`` must be
set before the CPU client is created (env is read at backend-init time, which
pytest's conftest-first import order guarantees). The persistent compile cache
is off: sessions point it at ``<repo>/.jax_cache``, and CPU test programs have
no business there.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

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
