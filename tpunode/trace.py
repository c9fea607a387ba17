"""Tracing/profiling hooks: spans + optional on-device profiler capture.

The reference has no tracing at all (SURVEY.md §5 "Tracing / profiling:
absent"); this module supplies what the TPU build needs to report the
BASELINE metrics honestly:

* :class:`span` — a context manager that times a region into the metrics
  registry: durations land in the ``span.<name>`` histogram (p50/p90/p99
  via ``metrics.histogram("span.<name>").quantile``) plus the legacy
  ``span.<name>.seconds`` / ``.count`` counters.  While a
  :func:`profile_to` capture is active it also emits a
  ``jax.profiler.TraceAnnotation`` so the region shows up named on the
  TensorBoard/perfetto timeline of the device trace.
* :func:`profile_to` — wraps ``jax.profiler.trace``: capture a full device
  profile into a directory (``TPUNODE_PROFILE=<dir>`` in bench.py).

When a request-scoped trace is active (tpunode/tracectx.py — one
per-block/tx pipeline trace), every span additionally lands as a child
node in that trace's tree, so the same instrumented regions feed both the
aggregate histograms and the causal per-item view.

Spans are deliberately cheap — a slotted context-manager class, two
``perf_counter`` calls, one ContextVar read and one locked registry
update, with the profiler annotation skipped outside an active capture —
so they can wrap the per-batch hot path (< 5µs per entry with no active
trace, pinned by tests/test_bench.py).  ``TPUNODE_NO_METRICS=1``
(metrics.disabled) skips the metric timing entirely.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

from .metrics import metrics
from .tracectx import _ACTIVE as _active_trace

__all__ = ["span", "profile_to"]

# Bound by profile_to() for the length of a capture: importing this module
# (and so the whole tpunode package) stays jax-free, which lets worker
# processes that only sign or parse start without jax.
_jax_profiler = None

# True only inside a profile_to() capture: spans skip the per-entry
# TraceAnnotation construction otherwise (it costs ~2µs — measurable
# against the <5µs span budget, and useless without an active trace).
_profiling = False


# name -> ("span.<name>", "span.<name>.seconds", "span.<name>.count"):
# precomputed so the hot path allocates no strings per span entry.
_span_names: dict[str, tuple[str, str, str]] = {}


def _names(name: str) -> tuple[str, str, str]:
    keys = _span_names.get(name)
    if keys is None:
        keys = _span_names[name] = (
            f"span.{name}",
            f"span.{name}.seconds",
            f"span.{name}.count",
        )
    return keys


class span:
    """``with span("verify.dispatch"): ...`` — see module docstring."""

    __slots__ = ("_name", "_ann", "_t0", "_rec", "_tok")

    def __init__(self, name: str):
        self._name = name
        self._ann = None

    def __enter__(self) -> "span":
        # Active per-item trace (tracectx): record this region as a child
        # span and make it the parent of any nested spans.  One ContextVar
        # read on the no-trace fast path.
        act = _active_trace.get()
        if act is None:
            self._rec = None
        else:
            tr, parent = act
            self._rec = tr.begin(self._name, parent)
            self._tok = _active_trace.set((tr, self._rec.id))
        if _profiling and _jax_profiler is not None:
            try:
                ann = _jax_profiler.TraceAnnotation(self._name)
                ann.__enter__()
                self._ann = ann
            except Exception:  # profiler unavailable on this backend
                self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        if not metrics.disabled:
            keys = _names(self._name)
            metrics.time_span(keys[0], keys[1], keys[2], dt)
        rec = self._rec
        if rec is not None:
            rec.dur = dt
            _active_trace.reset(self._tok)
            self._rec = None
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False


@contextlib.contextmanager
def profile_to(directory: Optional[str]) -> Iterator[None]:
    """Capture a JAX device profile into ``directory`` (no-op when None or
    the profiler is unavailable).  Spans entered during the capture are
    annotated onto the device timeline."""
    global _profiling, _jax_profiler
    if not directory:
        yield
        return
    try:
        import jax.profiler

        cm = jax.profiler.trace(directory)
    except Exception:
        yield
        return
    _jax_profiler = jax.profiler
    _profiling = True
    try:
        with cm:
            yield
    finally:
        _profiling = False
