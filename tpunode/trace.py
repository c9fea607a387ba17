"""Tracing/profiling hooks: spans + optional on-device profiler capture.

The reference has no tracing at all (SURVEY.md §5 "Tracing / profiling:
absent"); this module supplies what the TPU build needs to report the
BASELINE metrics honestly:

* :class:`span` — a context manager that times a region into the metrics
  registry: durations land in the ``span.<name>`` histogram (p50/p90/p99
  via ``metrics.histogram("span.<name>").quantile``) plus the
  ``span.<name>.seconds`` / ``.count`` counters.  ``span(name, cpu=True)``
  also records the CPU time the entering thread spent inside it, in
  ``span.<name>.cpu_seconds`` (``time.thread_time``).  Wall less CPU is
  time the thread did not run — GIL, lock, blocking call — and means
  that only for a span with no ``await`` inside: across an ``await`` the
  thread runs other tasks.  It is asked for span by span because the
  clock is a system call: two reads cost 11 µs a span on the v5e's host
  (1.2 µs here), which at the per-message spans' 13k entries a second
  took a fifth off the mempool cell's rate (PERF.md, PR 24); the spans
  that take it are entered a few times a lane.
  While a :func:`profile_to` capture is active a span also emits a
  ``jax.profiler.TraceAnnotation`` so the region shows up named on the
  TensorBoard/perfetto timeline of the device trace.  The annotation is a
  complete event written at exit, so a span held across an ``await``
  keeps its own start and length however many others open and close on
  that thread meanwhile; one open when the capture starts or stops is
  in it from the capture's start or up to its stop.
* :func:`profile_to` — capture a device profile into a directory
  (``jax.profiler.start_trace`` / ``stop_trace``) with the Python tracer
  off; the callers are ``chipbench/harness.py`` and ``bench.py``.

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
import logging
import time
from typing import Iterator, Optional

from .metrics import metrics
from .tracectx import _ACTIVE as _active_trace

__all__ = ["span", "record_span", "record_span_totals", "open_annotation",
           "profile_to"]

log = logging.getLogger("tpunode.trace")

# Bound by profile_to() for the length of a capture: importing this module
# (and so the whole tpunode package) stays jax-free, which lets worker
# processes that only sign or parse start without jax.
_jax_profiler = None

# True only inside a profile_to() capture: spans skip the per-entry
# TraceAnnotation construction otherwise (it costs ~2µs — measurable
# against the <5µs span budget, and useless without an active trace).
_profiling = False

# The spans open right now.  A capture annotates those entered before it
# started (from its start on) and ends the annotations of those still open
# when it stops (up to its stop): the waits are held for hundreds of
# milliseconds, and without this the idle time at both edges of a few
# seconds' capture has no name (PERF.md, PR 24).  set.add / discard and
# list(set) are single operations under the GIL.
_open: set["span"] = set()


# name -> ("span.<name>", "span.<name>.seconds", "span.<name>.count",
# "span.<name>.cpu_seconds"): precomputed so the hot path allocates no
# strings per span entry.
_span_names: dict[str, tuple[str, str, str, str]] = {}


def _names(name: str) -> tuple[str, str, str, str]:
    keys = _span_names.get(name)
    if keys is None:
        keys = _span_names[name] = (
            f"span.{name}",
            f"span.{name}.seconds",
            f"span.{name}.count",
            f"span.{name}.cpu_seconds",
        )
    return keys


class span:
    """``with span("verify.dispatch"): ...`` — see module docstring."""

    __slots__ = ("_name", "_cpu", "_ann", "_t0", "_c0", "_rec", "_tok")

    def __init__(self, name: str, cpu: bool = False):
        self._name = name
        self._cpu = cpu
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
        if _profiling:
            self._annotate()
        _open.add(self)
        self._t0 = time.perf_counter()
        if self._cpu:  # inside the wall interval: CPU never reads above wall
            self._c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        cpu = time.thread_time() - self._c0 if self._cpu else None
        dt = time.perf_counter() - self._t0
        _open.discard(self)
        if not metrics.disabled:
            keys = _names(self._name)
            metrics.time_span(keys[0], keys[1], keys[2], dt, keys[3], cpu)
        rec = self._rec
        if rec is not None:
            rec.dur = dt
            _active_trace.reset(self._tok)
            self._rec = None
        self._end_annotation()
        return False

    # The annotation is a complete event, written by whichever thread ends
    # it: the capture's thread may begin or end one for a span of another
    # thread.  Ending it twice writes it once.

    def _annotate(self) -> None:
        if self._ann is None:
            self._ann = open_annotation(self._name)

    def _end_annotation(self) -> None:
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)


def open_annotation(name: str):
    """An entered ``TraceAnnotation`` while a :func:`profile_to` capture
    is active, else None.  For a region whose ends lie in different
    threads (the loop's holds: the sampler thread sees one begin, the
    loop ends it): whoever ends it calls ``__exit__(None, None, None)``
    once."""
    if not _profiling:
        return None
    try:
        ann = _jax_profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann
    except Exception:  # profiler unavailable on this backend
        return None


def record_span(name: str, seconds: float) -> None:
    """A span whose two ends lie in different calls (a peer lost in one
    message and back in another, a stall seen on one tick and answered on
    a later one): the caller kept the start and hands in the length.  The
    same histogram and counters as :class:`span`; no trace node, no
    annotation."""
    if not metrics.disabled:
        keys = _names(name)
        metrics.time_span(keys[0], keys[1], keys[2], seconds, keys[3], None)


def record_span_totals(name: str, seconds: float, count: float) -> None:
    """A span entered too often to pay a registry update an entry (the
    loop's wait in its selector: tens of thousands a second): the caller
    added up seconds and entries in plain attributes and hands in what
    came since it last did.  ``span.<name>.seconds`` / ``.count`` only:
    no histogram, no trace node, no annotation."""
    keys = _names(name)
    metrics.inc_batch(((keys[1], seconds, None), (keys[2], count, None)))


@contextlib.contextmanager
def profile_to(directory: Optional[str]) -> Iterator[None]:
    """Capture a JAX device profile into ``directory`` (no-op when None).
    Spans entered during the capture are annotated onto the device
    timeline.  The Python tracer is off: JAX's default traces every
    Python call, which cut a host-bound node to a third of its rate and
    stalled it for seconds at stop (PERF.md, PR 23).  A capture that
    cannot start is logged and counted (``trace.capture_failed``) and the
    body runs without one."""
    global _profiling, _jax_profiler
    if not directory:
        yield
        return
    try:
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # start_trace/stop_trace, not the jax.profiler.trace context
        # manager: a caller may have wrapped that one to pass options of
        # its own (chipbench/harness.py does)
        jax.profiler.start_trace(directory, profiler_options=opts)
    except Exception:
        log.exception("[Trace] profiler capture into %s did not start",
                      directory)
        metrics.inc("trace.capture_failed")
        yield
        return
    _jax_profiler = jax.profiler
    _profiling = True
    for sp in list(_open):
        sp._annotate()
    try:
        yield
    finally:
        _profiling = False
        for sp in list(_open):
            sp._end_annotation()
        jax.profiler.stop_trace()
