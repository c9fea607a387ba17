"""The "node loop" metrics (PR 38): each reads a number from a CPU
rehearsal of a cell that lists it, and nothing — not 0 — from the counters
of a commit that has no clock on its loop."""

import json
import time

import pytest

from chipbench import harness
from chipbench.tests.rehearse import rehearse

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
# PR 38's sixteen, in the order they were appended in
SIXTEEN = [
    "loop.idle_share", "loop.on_cpu_share", "loop.wait_share",
    "loop.hold_share", "loop.hold_share.ibd", "loop.hold_share.gc",
    "loop.hold_share.telemetry", "loop.hold_share.harness",
    "loop.long_holds_per_min", "gc.pause_share", "cpu.loop_share",
    "cpu.extract_share", "cpu.executor_share", "cpu.runtime_share",
    "cpu.runtime_ms_per_lane", "cpu.executor_ms_per_lane"]
NEW = [m for m in BENCH["per_layer"] if m["name"] in SIXTEEN]
CELLS = {wl["name"] for wl in BENCH["workloads"]}


def _reading(cell: str, counters: dict) -> dict:
    bench, wl, cfg, tr = harness.load_cell(cell)
    ctx = harness.Ctx(wl, bench, cfg, tr, 1, 1.0, False, harness.Rehearsal(),
                      time.monotonic())
    return harness.read_per_layer(
        ctx, harness.Reading(counters, 40.0, None, {}, {}))


def test_the_entries_are_the_issues_and_each_has_its_file():
    # all there, in that order among themselves, under one layer; what
    # later PRs append may stand after them, between them or in their layer
    assert [m["name"] for m in NEW] == SIXTEEN
    assert {m["layer"] for m in NEW} == {"node loop"}
    lat = {m["name"] for m in BENCH["end_to_end"]
           if m["name"] == "verdict_p50_ms"}
    for m in NEW:
        spec = harness.load_json(ROOT, "chipbench", "metrics",
                                 m["name"] + ".json")
        assert {k: spec[k] for k in ("layer", "unit", "better", "source",
                                     "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}
        due = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(due.get("workloads", CELLS)), m
    assert lat


@pytest.mark.parametrize("cell", ["bch-node.ibd", "bch-node.mempool"])
def test_every_new_metric_reads_a_number_on_a_rehearsal(cell, capfd):
    res = rehearse(cell)
    assert res["correct"] is True
    out = capfd.readouterr().out
    line = next(json.loads(ln) for ln in out.splitlines()
                if ln.startswith('{"line": "per_layer_untraced"'))
    want = [m["name"] for m in NEW if cell in m["workloads"]]
    assert len(want) >= 9
    for name in want:
        assert isinstance(line.get(name), float), (name, line.get(name))
    parts = [line[f"loop.{p}_share"] for p in ("idle", "on_cpu", "wait")]
    assert sum(parts) == pytest.approx(100.0, abs=1e-6)
    assert all(0.0 <= p <= 100.0 for p in parts), parts
    if "cpu.loop_share" in want:
        shares = [line[f"cpu.{r}_share"]
                  for r in ("loop", "extract", "executor", "runtime")]
        assert all(s >= 0.0 for s in shares) and 50.0 < sum(shares) <= 100.0 + 1e-6
        assert line["cpu.executor_ms_per_lane"] > 0.0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_commit_without_the_clock_reads_nothing(cell):
    """The parent's side of the driver's pair: the counters it has, none
    of this PR's."""
    parent = {"sched.lanes": 700.0, "span.verify.prepare.seconds": 7.0,
              "verify.tpu_items": 1e6, "node.verify_txs": 1e5}
    got = _reading(cell, parent)
    assert not {m["name"] for m in NEW} & set(got), got
    # and with the clock on, a window in which nothing moved reads zeros
    quiet = dict(parent, **{
        "span.loop.idle.seconds": 30.0, "span.loop.idle.count": 4e5,
        "span.loop.hold.seconds": 0.0, "span.loop.hold.count": 0.0,
        "loop.cpu_seconds": 8.0, "loop.holds_long": 0.0,
        "cpu.process_seconds": 50.0})
    for where in ("gc", "telemetry", "harness"):
        quiet[f'loop.hold_seconds{{where="{where}"}}'] = 0.0
    for gen in "012":
        quiet[f'gc.pause_seconds{{gen="{gen}"}}'] = 0.01
    for role, s in (("loop", 8.0), ("extract", 12.0), ("executor", 14.0),
                    ("runtime", 14.0), ("store", 1.0), ("python_other", 1.0)):
        quiet[f'cpu.seconds{{role="{role}"}}'] = s
    got = {k: v["value"] for k, v in _reading(cell, quiet).items()}
    assert {m["name"] for m in NEW if cell in m["workloads"]} <= set(got)
    assert got["loop.idle_share"] == 75.0 and got["loop.on_cpu_share"] == 20.0
    assert got["loop.wait_share"] == pytest.approx(5.0)
    assert got["gc.pause_share"] == pytest.approx(0.075)
    if "loop.long_holds_per_min" in got:
        assert got["loop.long_holds_per_min"] == 0.0
        assert got["loop.hold_share.gc"] == 0.0
    if "cpu.runtime_ms_per_lane" in got:
        assert got["cpu.runtime_ms_per_lane"] == 20.0
        assert got["cpu.runtime_share"] == 28.0
