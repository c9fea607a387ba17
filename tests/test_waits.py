"""The waits have names (ISSUE 24): the scheduler's three idle states, the
lane's life on the loop side and its delivery, each span's CPU time, and a
``profile_to`` that takes the capture it is used for.  CPU only; the
engine runs its oracle/cpu rungs with a slowed or broken dispatch standing
in for the device."""

import asyncio
import glob
import os
import threading
import time

import pytest

from tpunode import trace
from tpunode.metrics import metrics
from tpunode.verify import engine as engine_mod
from tpunode.verify.engine import VerifyConfig, VerifyEngine

from tests.test_engine import make_items

SCHED = ("sched.starved", "sched.linger", "sched.slot_wait")


@pytest.fixture
def intervals(monkeypatch):
    """Every span the engine module enters, as (name, start, end)."""
    log: list = []

    class Recorded(trace.span):
        def __exit__(self, *exc):
            end = time.perf_counter()
            out = super().__exit__(*exc)
            log.append((self._name, self._t0, end))
            return out

    monkeypatch.setattr(engine_mod, "span", Recorded)
    return log


def _slow(eng, seconds: float) -> None:
    orig = eng._dispatch_multi

    def slow(payloads, target=None):
        time.sleep(seconds)
        return orig(payloads, target)

    eng._dispatch_multi = slow


@pytest.mark.asyncio
async def test_scheduler_states_never_overlap_and_cover_the_lifetime(intervals):
    """An engine that sits idle, then takes lanes that have to wait for
    the one pipeline slot, then lingers over a lone item: at every moment
    of its life at most one of the three states is open, together they
    cover >= 95% of it, and each of the three was really entered."""
    batches = [make_items(4) for _ in range(3)]
    lone, lone_exp = make_items(1)
    async with VerifyEngine(
        VerifyConfig(backend="oracle", batch_size=4, max_wait=0.03,
                     pipeline_depth=1)
    ) as eng:
        _slow(eng, 0.03)
        born = time.perf_counter()
        await asyncio.sleep(0.15)  # idle: starved
        got = await asyncio.gather(*(eng.verify(i) for i, _ in batches))
        assert list(got) == [e for _, e in batches]
        assert await eng.verify(lone) == lone_exp  # under target: lingers
        died = time.perf_counter()
    states = sorted((s, e, n) for n, s, e in intervals if n in SCHED)
    for (_, end, a), (start, _, b) in zip(states, states[1:]):
        assert start >= end, f"{a} still open when {b} opened"
    covered = sum(max(0.0, min(e, died) - max(s, born)) for s, e, _ in states)
    assert covered >= 0.95 * (died - born), (covered, died - born)
    total = {n: sum(e - s for s, e, m in states if m == n) for n in SCHED}
    assert total["sched.starved"] >= 0.12
    assert total["sched.slot_wait"] >= 0.03  # lanes 2 and 3 waited for lane 1
    assert total["sched.linger"] >= 0.02  # the lone item waited out max_wait


@pytest.mark.asyncio
async def test_every_lane_is_one_lane_span_and_one_delivery(intervals):
    """Sliced, failed and cancelled lanes: ``span.verify.lane.count`` =
    ``span.verify.deliver.count`` = ``sched.lanes``, and the delivery lies
    inside its lane span."""
    metrics.reset()
    sliced, sliced_exp = make_items(6, tamper_every=4)  # lanes of 4 + 2
    doomed, _ = make_items(3)
    stuck, _ = make_items(2)
    release = threading.Event()
    async with VerifyEngine(
        VerifyConfig(backend="oracle", batch_size=4, max_wait=0.0,
                     pipeline_depth=2)
    ) as eng:
        assert await eng.verify(sliced) == sliced_exp
        orig = eng._dispatch_multi

        def broken(payloads, target=None):
            raise RuntimeError("all rungs down")

        eng._dispatch_multi = broken
        with pytest.raises(RuntimeError, match="all rungs down"):
            await eng.verify(doomed)

        def wedged(payloads, target=None):
            release.wait(5)
            return orig(payloads, target)

        eng._dispatch_multi = wedged
        waiter = asyncio.ensure_future(eng.verify(stuck))
        while not eng.dispatch_inflight():
            await asyncio.sleep(0.005)
    release.set()  # the thread behind the cancelled lane ends
    with pytest.raises(asyncio.CancelledError):
        await waiter
    lanes = metrics.get("sched.lanes")
    assert lanes == 4  # 2 sliced + 1 failed + 1 cancelled
    assert metrics.get("span.verify.lane.count") == lanes
    assert metrics.get("span.verify.deliver.count") == lanes
    lane_iv = [(s, e) for n, s, e in intervals if n == "verify.lane"]
    for n, s, e in intervals:
        if n == "verify.deliver":
            assert any(ls <= s and e <= le for ls, le in lane_iv)


@pytest.mark.parametrize("how", ["sleeps", "spins", "not asked"])
def test_span_cpu_seconds_is_the_threads_own(how):
    """``span(name, cpu=True)``: wall less CPU is the time the thread did
    not run — a span that sleeps has next to no CPU time, one that spins
    has a good share of its wall time, and none has more CPU than wall.
    A span that does not ask pays for no clock and records none.

    The suite runs six workers beside this one, and they take the core
    away from a spin for tens of milliseconds at a time: so the spin is
    0.2 s long, a third of it on the CPU is enough, and it gets five
    tries (the sleeping span's CPU time stays a twentieth of that)."""
    name = f"unit-cpu-{how.replace(' ', '-')}"
    length = 0.2 if how == "spins" else 0.05

    def once() -> tuple:
        wall0 = metrics.get(f"span.{name}.seconds")
        cpu0 = metrics.get(f"span.{name}.cpu_seconds")
        with trace.span(name, cpu=how != "not asked"):
            if how == "sleeps":
                time.sleep(length)
            else:
                until = time.perf_counter() + length
                while time.perf_counter() < until:
                    pass
        wall = metrics.get(f"span.{name}.seconds") - wall0
        cpu = metrics.get(f"span.{name}.cpu_seconds") - cpu0
        assert wall >= length
        # one tick of the coarsest CPU clock met (10 ms, the v5e's host)
        assert 0.0 <= cpu <= wall + 0.01
        return wall, cpu

    wall, cpu = once()
    if how == "sleeps":
        assert cpu < 0.01
    elif how == "spins":
        for _ in range(4):
            if cpu > 0.3 * wall:
                break
            wall, cpu = once()
        assert cpu > 0.3 * wall
    else:
        assert f"span.{name}.cpu_seconds" not in metrics.snapshot()


def test_span_cpu_seconds_never_exceeds_wall_in_an_engine_run():
    """Over a real run the spans with no ``await`` inside — the dispatch
    thread's and the delivery — carry ``cpu_seconds`` <= ``seconds``; the
    waits held across ``await``s, where the figure would count other
    tasks' work, carry none."""
    metrics.reset()
    items, expected = make_items(5, tamper_every=2)

    async def go():
        async with VerifyEngine(
            VerifyConfig(backend="oracle", batch_size=4, max_wait=0.0)
        ) as eng:
            return await eng.verify(items)

    assert asyncio.run(go()) == expected
    snap = metrics.snapshot()
    for n in ("verify.dispatch", "verify.deliver"):
        assert 0.0 <= snap[f"span.{n}.cpu_seconds"] <= snap[f"span.{n}.seconds"]
    for n in SCHED + ("verify.lane",):
        assert snap[f"span.{n}.count"] >= 1
        assert f"span.{n}.cpu_seconds" not in snap


def _xplanes(directory) -> list:
    return glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb"))


def test_profile_to_captures_under_the_harness_lambda(tmp_path):
    """``chipbench/harness.py`` swaps ``jax.profiler.trace`` for a
    one-argument lambda while it calls ``profile_to``: the capture must
    start all the same, with the Python tracer off by ``profile_to``'s own
    doing, and a span held open inside it must come out of the trace."""
    import jax
    from jax.profiler import ProfileData

    plain = jax.profiler.trace
    failed = metrics.get("trace.capture_failed")
    jax.profiler.trace = lambda d: plain(d)  # would refuse profiler_options
    try:
        with trace.profile_to(str(tmp_path)):
            assert trace._profiling
            with trace.span("unit-captured"):
                time.sleep(0.01)
    finally:
        jax.profiler.trace = plain
    assert not trace._profiling
    assert metrics.get("trace.capture_failed") == failed
    found = _xplanes(tmp_path)
    assert len(found) == 1
    data = ProfileData.from_file(found[0])
    events = [(plane.name, ev.name, ev.duration_ns)
              for plane in data.planes for ln in plane.lines
              for ev in ln.events]
    ours = [d for _, n, d in events if n == "unit-captured"]
    assert len(ours) == 1 and ours[0] >= 10e6
    # the Python tracer names every call "$<file>:<line> <function>"
    assert not [n for _, n, _ in events if n.startswith("$")]


def test_spans_open_at_the_captures_edges_are_in_it(tmp_path):
    """A wait is held for hundreds of milliseconds: one entered before the
    capture started comes out of it from the capture's start on, one
    still open when it stops comes out up to the stop, and that one's own
    exit afterwards still records its whole length in the registry."""
    from jax.profiler import ProfileData

    early = trace.span("unit-open-before")
    late = trace.span("unit-open-after")
    wall0 = metrics.get("span.unit-open-after.seconds")
    early.__enter__()
    time.sleep(0.05)  # before the capture: not in the event
    with trace.profile_to(str(tmp_path)):
        time.sleep(0.02)
        early.__exit__(None, None, None)
        late.__enter__()
        time.sleep(0.03)
    time.sleep(0.05)  # after the capture: not in the event
    late.__exit__(None, None, None)
    assert metrics.get("span.unit-open-after.seconds") - wall0 >= 0.08
    assert not trace._open, sorted(s._name for s in trace._open)
    data = ProfileData.from_file(_xplanes(tmp_path)[0])
    ms = {}
    for plane in data.planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("unit-open-"):
                    ms.setdefault(ev.name, []).append(ev.duration_ns / 1e6)
    assert len(ms["unit-open-before"]) == 1 and len(ms["unit-open-after"]) == 1
    assert 20.0 <= ms["unit-open-before"][0] < 50.0
    assert 30.0 <= ms["unit-open-after"][0] < 60.0


def test_profile_to_that_cannot_start_is_loud(tmp_path, caplog):
    """A second capture while one runs cannot start: the body still runs,
    ``trace.capture_failed`` counts it and the log says so; the capture
    that did start is whole."""
    failed = metrics.get("trace.capture_failed")
    ran = []
    with trace.profile_to(str(tmp_path / "outer")):
        with caplog.at_level("ERROR", logger="tpunode.trace"):
            with trace.profile_to(str(tmp_path / "inner")):
                ran.append(trace._profiling)
        assert trace._profiling  # the outer capture is still on
    assert ran == [True]
    assert metrics.get("trace.capture_failed") == failed + 1
    assert "did not start" in caplog.text
    assert len(_xplanes(tmp_path / "outer")) == 1
    assert not _xplanes(tmp_path / "inner")
