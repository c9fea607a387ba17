"""asyncsan runtime-sanitizer tests (ISSUE 3): TPUNODE_ASYNCSAN loop
debug mode, the blocked-loop attributor, the task-supervision registry's
leak reporting, and the fakenet integration where a deliberately-injected
blocking call and leaked task are caught at runtime (their static twins
are caught by the analyzer — cross-checked here too)."""

from __future__ import annotations

import asyncio
import time

import pytest

from tpunode import asyncsan
from tpunode.actors import TaskRegistry, spawn_supervised, task_registry
from tpunode.analysis import analyze_source
from tpunode.events import EventLog, events
from tpunode.watchdog import Watchdog, WatchdogConfig


# --- env gate + install ------------------------------------------------------


def test_enabled_env_gate(monkeypatch):
    monkeypatch.delenv("TPUNODE_ASYNCSAN", raising=False)
    assert not asyncsan.enabled()
    for off in ("0", "false", "no", ""):
        monkeypatch.setenv("TPUNODE_ASYNCSAN", off)
        assert not asyncsan.enabled()
    monkeypatch.setenv("TPUNODE_ASYNCSAN", "1")
    assert asyncsan.enabled()


@pytest.mark.asyncio
async def test_install_wires_debug_mode():
    loop = asyncio.get_running_loop()
    try:
        asyncsan.install()
        assert loop.get_debug() is True
        assert loop.slow_callback_duration == asyncsan.slow_callback_duration()
    finally:
        loop.set_debug(False)


@pytest.mark.asyncio
async def test_slow_callback_threshold_env_read_at_install(monkeypatch):
    """TPUNODE_ASYNCSAN_SLOW is read at install time (like the
    TPUNODE_ASYNCSAN gate itself), not frozen at import."""
    loop = asyncio.get_running_loop()
    monkeypatch.setenv("TPUNODE_ASYNCSAN_SLOW", "0.025")
    try:
        asyncsan.install()
        assert loop.slow_callback_duration == 0.025
    finally:
        loop.set_debug(False)
    monkeypatch.setenv("TPUNODE_ASYNCSAN_SLOW", "garbage")
    assert asyncsan.slow_callback_duration() == asyncsan.SLOW_CALLBACK_DURATION


# --- blocked-loop attributor -------------------------------------------------


@pytest.mark.asyncio
async def test_attributor_captures_blocking_frame():
    att = asyncsan.LoopAttributor(threshold=0.05, interval=0.02)
    att.start()
    try:
        await asyncio.sleep(0.1)  # let the selector stamps+sampler establish
        time.sleep(0.4)  # the deliberate sync freeze
        await asyncio.sleep(0.05)
        blocked = att.last_blocked()
        assert blocked is not None
        assert blocked["age_seconds"] >= 0.05
        # innermost frame names THIS test as the offender
        assert any("test_asyncsan" in f for f in blocked["frames"]), blocked
    finally:
        att.stop()
    assert att._thread is None  # stop() joins the sampler


@pytest.mark.asyncio
async def test_attributor_quiet_loop_reports_nothing():
    att = asyncsan.LoopAttributor(threshold=0.5, interval=0.02)
    att.start()
    try:
        await asyncio.sleep(0.15)
        assert att.last_blocked() is None
    finally:
        att.stop()


def test_watchdog_merges_attribution_into_stall_event():
    class FakeAttributor:
        max_age = None

        def last_blocked(self, max_age=120.0):
            self.max_age = max_age
            return {
                "age_seconds": 1.5,
                "frames": ["node.py:123 in _drain"],
            }

    log = EventLog()
    att = FakeAttributor()
    wd = Watchdog(
        WatchdogConfig(interval=1.0, lag_threshold=0.5),
        log_=log,
        attributor=att,
    )
    (ev,) = wd.check(lag=2.0)
    assert ev["kind"] == "event_loop"
    assert ev["blocked_frames"] == ["node.py:123 in _drain"]
    assert ev["blocked_age_seconds"] == 1.5
    # the capture window is scoped to THIS episode (lag + 2 intervals),
    # so a stale capture from an earlier stall can't blame the wrong code
    assert att.max_age == pytest.approx(2.0 + 2 * 1.0)
    # without an attributor the event shape is unchanged (PR 2 behavior)
    wd2 = Watchdog(WatchdogConfig(lag_threshold=0.5), log_=EventLog())
    (ev2,) = wd2.check(lag=2.0)
    assert "blocked_frames" not in ev2


# --- task-supervision registry ----------------------------------------------


@pytest.mark.asyncio
async def test_registry_reports_unowned_pending_task_once():
    reg = TaskRegistry()
    log = EventLog()
    leaky = reg.spawn(asyncio.sleep(30), name="leaky")
    ok = reg.spawn(asyncio.sleep(0), name="done-in-time")
    await asyncio.sleep(0.01)  # "done-in-time" completes and deregisters
    leaks = reg.report_leaks(log_=log)
    assert [e["task"] for e in leaks] == ["leaky"]
    assert leaks[0]["type"] == "asyncsan.task_leak"
    assert "test_asyncsan.py:" in leaks[0]["where"]  # spawn-site attribution
    # one report per leak: the second sweep is silent
    assert reg.report_leaks(log_=log) == []
    assert log.counts() == {"asyncsan.task_leak": 1}
    leaky.cancel()
    assert ok.done()


@pytest.mark.asyncio
async def test_registry_owner_scoping():
    """A pending task whose owner is alive and open is supervised, not
    leaked; a closing or garbage-collected owner orphans it."""

    class Owner:
        _closing = False

    reg = TaskRegistry()
    log = EventLog()
    owner = Owner()
    t1 = reg.spawn(asyncio.sleep(30), name="supervised", owner=owner)
    assert reg.report_leaks(log_=log) == []  # live open owner
    owner._closing = True
    assert [e["task"] for e in reg.report_leaks(log_=log)] == ["supervised"]
    t1.cancel()

    owner2 = Owner()
    t2 = reg.spawn(asyncio.sleep(30), name="orphaned", owner=owner2)
    del owner2  # owner garbage-collected while its task still runs
    assert [e["task"] for e in reg.report_leaks(log_=log)] == ["orphaned"]
    t2.cancel()


@pytest.mark.asyncio
async def test_supervisor_and_linked_tasks_register_children():
    """actors' Supervisor/LinkedTasks spawn through the registry with
    themselves as owner: tracked while alive, never misreported."""
    from tpunode.actors import LinkedTasks, Supervisor

    async def forever():
        await asyncio.sleep(30)

    async with Supervisor(name="s") as sup:
        child = sup.add_child(forever(), name="sup-child")
        assert child in task_registry.live()
        assert task_registry.report_leaks(log_=EventLog()) == []
    assert child not in task_registry.live()  # cancelled+deregistered

    lt = LinkedTasks(name="lt")
    linked = lt.link(forever(), name="lt-child")
    assert linked in task_registry.live()
    await lt.aclose()
    assert linked not in task_registry.live()


# --- static/runtime cross-check ---------------------------------------------


def test_injected_hazards_also_caught_statically():
    """The same two defects the fakenet test injects at runtime are
    caught by the analyzer at lint time — and silenced by the documented
    suppression pragma (the satellite's unit half)."""
    src = """\
import asyncio
import time
from tpunode.actors import spawn_supervised

async def main():
    spawn_supervised(asyncio.sleep(30))
    time.sleep(0.9)
"""
    assert {f.rule for f in analyze_source(src)} == {
        "dropped-task", "blocking-call",
    }
    suppressed = src.replace(
        "spawn_supervised(asyncio.sleep(30))",
        "spawn_supervised(asyncio.sleep(30))  # asyncsan: disable=dropped-task",
    ).replace(
        "time.sleep(0.9)",
        "time.sleep(0.9)  # asyncsan: disable=blocking-call",
    )
    assert analyze_source(suppressed) == []


# --- fakenet integration -----------------------------------------------------


@pytest.mark.asyncio
async def test_node_sanitizers_catch_injected_block_and_leak(monkeypatch):
    """ISSUE 3 satellite (integration half): a real fakenet node under
    TPUNODE_ASYNCSAN=1 — a deliberate sync block of the event loop
    produces a watchdog.stall event ATTRIBUTED to the offending frame,
    and a deliberately-orphaned supervised task produces an
    asyncsan.task_leak event at node shutdown."""
    from tests.fakenet import dummy_peer_connect, poll_until as _poll
    from tests.fixtures import all_blocks
    from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher
    from tpunode.store import MemoryKV

    monkeypatch.setenv("TPUNODE_ASYNCSAN", "1")
    events.reset()
    pub = Publisher(name="san-events")
    cfg = NodeConfig(
        net=BCH_REGTEST,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:18333"],
        connect=lambda sa: dummy_peer_connect(BCH_REGTEST, all_blocks()),
        stats_interval=0,
        watchdog_interval=0.05,
    )
    loop = asyncio.get_running_loop()
    try:
        async with pub.subscription():
            async with Node(cfg) as node:
                # debug mode + attributor wired by the env gate
                assert loop.get_debug() is True
                assert node._attributor is not None
                assert node._watchdog.attributor is node._attributor
                await asyncio.sleep(0.15)  # selector-stamp/watchdog baseline
                # inject the two defects
                leaked = spawn_supervised(
                    asyncio.sleep(30), name="leaky-test-task"
                )
                time.sleep(0.9)  # deliberate blocking call on the loop
                await _poll(
                    lambda: any(
                        e.get("kind") == "event_loop"
                        for e in events.tail(50, type="watchdog.stall")
                    ),
                    what="attributed watchdog.stall",
                )
                ev = [
                    e for e in events.tail(50, type="watchdog.stall")
                    if e.get("kind") == "event_loop"
                ][-1]
                assert ev["lag_seconds"] >= 0.5
                frames = ev.get("blocked_frames")
                assert frames, f"stall event not attributed: {ev}"
                assert any("test_asyncsan" in f for f in frames), frames
        # node shutdown swept the orphan into a task_leak event
        leaks = events.tail(50, type="asyncsan.task_leak")
        assert any(e["task"] == "leaky-test-task" for e in leaks), leaks
        assert not leaked.done()
        leaked.cancel()
    finally:
        loop.set_debug(False)
