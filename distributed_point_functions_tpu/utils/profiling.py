"""Profiling hooks — the TPU framework's tracing story (SURVEY.md §5).

The reference's performance introspection is google/benchmark binaries
plus wall-clock timing in `experiments/synthetic_data_benchmarks.cc`; on
TPU the equivalent first-class tool is the JAX profiler (xprof traces
viewable in TensorBoard/Perfetto). This module wraps it so servers and
benchmarks can capture device traces without importing profiler
internals:

    with trace("/tmp/dpf-trace"):
        server.handle_request(request)

    with annotate("expand"):          # named region inside a trace
        selections = evaluate_selection_blocks(...)

    @jax.jit
    @scoped("pir.expand")             # program scope of the traced ops
    def expand(...): ...

Both are no-ops (with a one-time log) if the profiler is unavailable,
so library code can call them unconditionally.
"""

from __future__ import annotations

import contextlib
import functools
import logging

logger = logging.getLogger(__name__)
_warned = False

# `annotate()` sits on the per-request serving hot path (every span is
# one), so the profiler module resolves once and is cached — including
# the unavailable case, so a broken install doesn't retry the import
# per request. `_UNRESOLVED` (not None) is the sentinel because None is
# the cached "unavailable" answer.
_UNRESOLVED = object()
_prof_module = _UNRESOLVED


def _profiler():
    global _warned, _prof_module
    if _prof_module is _UNRESOLVED:
        try:
            import jax.profiler as prof

            _prof_module = prof
        except Exception:  # pragma: no cover - profiler ships with jax
            _prof_module = None
            if not _warned:
                _warned = True
                logger.info("jax.profiler unavailable; tracing disabled")
    return _prof_module


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_trace: bool = False):
    """Capture a device trace of the enclosed block into `log_dir`."""
    prof = _profiler()
    if prof is None:
        yield
        return
    prof.start_trace(log_dir, create_perfetto_trace=create_perfetto_trace)
    try:
        yield
    finally:
        prof.stop_trace()


def annotate(name: str):
    """Named sub-region (TraceAnnotation) inside an active trace."""
    prof = _profiler()
    if prof is None:
        return contextlib.nullcontext()
    return prof.TraceAnnotation(name)


_is_capturing = _UNRESOLVED


def profiler_active() -> bool:
    """Whether a profiler session is capturing host annotations right
    now (one native call; resolved once, like `annotate`)."""
    global _is_capturing
    if _is_capturing is _UNRESOLVED:
        prof = _profiler()
        _is_capturing = getattr(
            getattr(prof, "TraceAnnotation", None), "is_enabled", None
        ) or (lambda: False)
    return _is_capturing()


def scoped(name: str):
    """Decorator for a function that a `jax.jit` traces: its ops run
    under `jax.named_scope(name)`, so every op of the compiled program
    carries `name` in its HLO metadata (`op_name`), and a device profile
    shows it as the op's program scope. Metadata only: the compiled
    program is the same."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return run

    return wrap
