"""The finite backlogs (PR 40): how many blocks a run is given is a plain
function of (traffic file, seconds) in the mix's driver module
(``backlog()``); every backlog holds its window and its traced capture to
1.5 times the rate its file says was measured; a cell says how much was
left (``backlog.left_share``); a traced run whose backlog is over before
its capture begins exits with a line that says so, and prints no result.
And the plain reference is the one the configuration names."""

import asyncio
import importlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import gen, harness
from chipbench.tests.rehearse import CPU, rehearse, tiny

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
ENTRY = next(m for m in BENCH["per_layer"] if m["name"] == "backlog.left_share")
BACKLOG = ENTRY["workloads"]  # the cells whose traffic is a finite backlog
IBD = [c for c in BACKLOG if c.endswith((".ibd", ".ibd-spend", ".ibd-faults"))]
SECONDS = BENCH["run_seconds"]
ROOM = 1.5


def _sized(cell: str, seconds: float = SECONDS) -> tuple:
    _, wl, _, traffic = harness.load_cell(cell)
    driver = importlib.import_module("chipbench.drivers." + traffic["driver"])
    return traffic, driver.backlog(traffic, seconds)


def test_the_backlog_cells_are_the_five_and_no_other_driver_has_one():
    assert sorted(BACKLOG) == ["bch-32mb.blocks", "bch-32mb.single",
                               "bch-node.ibd", "bch-utxo.ibd-spend",
                               "bch-wan.ibd-faults"]
    assert len(IBD) == 3
    for wl in BENCH["workloads"]:
        traffic = harness.load_json(ROOT, "chipbench", "traffic",
                                    wl["traffic"] + ".json")
        driver = importlib.import_module(
            "chipbench.drivers." + traffic["driver"])
        assert hasattr(driver, "backlog") == (wl["name"] in BACKLOG), wl
        assert ("backlog" in traffic) == (wl["name"] in BACKLOG), wl


@pytest.mark.parametrize("cell", BACKLOG)
def test_window_and_capture_hold_to_one_and_a_half_times_the_measured_rate(cell):
    traffic, b = _sized(cell)
    assert b["measured"] == traffic["backlog"]["measured_sigs_per_s"]
    assert "ledger" in traffic["backlog"]["note"]  # the rate names its origin
    assert b["window_holds_to"] >= ROOM * b["measured"], b
    # a window that runs whole leaves its capture whole
    assert b["capture_holds_to"] >= b["window_holds_to"]
    # and the arithmetic, once more by hand
    sigs_block = gen.totals(traffic["mix"], traffic["txs_per_block"])["sigs"]
    assert b["sigs"] == b["blocks"] * sigs_block
    span = traffic["trace_seconds"]
    if cell in IBD:
        steady = traffic["steady_until_share"] * b["sigs"]
        ramp = traffic["ramp_seconds"]
        assert b["window_holds_to"] == pytest.approx(steady / (SECONDS + ramp))
        assert b["capture_holds_to"] == pytest.approx(
            steady / (SECONDS + ramp - span))
    else:
        left = (b["blocks"] - traffic["outstanding"]
                - traffic["backlog"]["ramp_allowance_blocks"])
        assert traffic["backlog"]["ramp_allowance_blocks"] >= traffic["ramp_blocks"]
        assert b["window_holds_to"] == pytest.approx(left * sigs_block / SECONDS)
        assert b["capture_holds_to"] == pytest.approx(
            left * sigs_block / (SECONDS - span))


def test_the_issues_counts():
    """IBD: one chain of >= 12,600 blocks that holds to >= 39k sigs/s;
    blocks: >= 42 left to send (1.05 blocks/s); single: >= 34 (0.83)."""
    chains = {cell: _sized(cell)[1] for cell in IBD}
    assert len({b["blocks"] for b in chains.values()}) == 1, chains
    assert len({b["sigs"] for b in chains.values()}) == 1
    one = next(iter(chains.values()))
    assert one["blocks"] >= 12600 and one["window_holds_to"] >= 39000
    for cell, left, rate in (("bch-32mb.blocks", 42, 1.05),
                             ("bch-32mb.single", 34, 0.83)):
        traffic, b = _sized(cell)
        sigs_block = gen.totals(traffic["mix"], traffic["txs_per_block"])["sigs"]
        assert sigs_block == 150012
        assert b["window_holds_to"] / sigs_block >= rate
        assert (b["blocks"] - traffic["outstanding"]
                - traffic["backlog"]["ramp_allowance_blocks"]) >= left
    # what the mix is stays what it was
    for cell in BACKLOG:
        traffic, _ = _sized(cell)
        assert traffic["mix"]["adversarial_every"] == 128
        assert traffic["trace_seconds"] == 4.0
        assert traffic["txs_per_block"] in (64, 66672)


@pytest.mark.parametrize("cell", BACKLOG)
def test_the_block_count_is_a_function_of_the_file_and_the_seconds(cell):
    """The driver is given what the function says, at any length."""
    bench, wl, cfg, traffic = harness.load_cell(cell)
    driver = importlib.import_module("chipbench.drivers." + traffic["driver"])
    counts = [driver.backlog(traffic, s)["blocks"] for s in (10, 20, 40)]
    assert counts == sorted(counts) and counts[0] < counts[-1]
    small = harness.deep_merge(traffic, tiny(wl["traffic"]))
    ctx = harness.Ctx(wl, bench, cfg, small, 3, 3.0, False,
                      harness.Rehearsal(), time.monotonic())
    made = driver.backlog(small, 3.0)["blocks"]
    if traffic["driver"] in ("ibd", "blocks"):  # the others want a program
        assert driver.Driver(ctx).n_blocks == made
    assert 20 <= made <= 400  # a rehearsal's size


# ---- backlog.left_share ------------------------------------------------------


def _reading(cell: str, samples: dict) -> dict:
    bench, wl, cfg, tr = harness.load_cell(cell)
    ctx = harness.Ctx(wl, bench, cfg, tr, 1, 1.0, False, harness.Rehearsal(),
                      time.monotonic())
    return harness.read_per_layer(
        ctx, harness.Reading({}, 40.0, None, samples, {}))


@pytest.mark.parametrize("cell", [wl["name"] for wl in BENCH["workloads"]])
def test_left_share_is_read_where_it_is_listed_and_nowhere_else(cell):
    spec = harness.load_json(ROOT, "chipbench", "metrics",
                             "backlog.left_share.json")
    assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")
            } == {k: ENTRY[k] for k in ("layer", "unit", "better", "source",
                                        "moves")}
    assert ENTRY["layer"] == "peers (harness)" and ENTRY["unit"] == "%"
    got = _reading(cell, {"backlog_left_share": [40.9]})
    if cell in BACKLOG:
        assert got["backlog.left_share"] == {"value": 40.9, "unit": "%"}
    else:
        assert "backlog.left_share" not in got
    # a driver that gave no sample (the parent's): nothing, not 0
    assert "backlog.left_share" not in _reading(cell, {})


class _Mark:
    def __init__(self, t, n):
        self.t, self.cpu, self.n_verdicts = t, t / 10, n


def test_the_ibd_drivers_sample_is_the_chains_unverified_share():
    from chipbench.drivers import ibd

    bench, wl, cfg, tr = harness.load_cell("bch-node.ibd")
    tr = harness.deep_merge(tr, tiny("ibd"))
    ctx = harness.Ctx(wl, bench, cfg, tr, 1, 3.0, False, harness.Rehearsal(),
                      time.monotonic())
    d = ibd.Driver(ctx)
    d.offered.expect = {bytes([i]): () for i in range(200)}
    d.totals, d.served, d.utxo_height = {"sigs": 144 * d.n_blocks}, [], 0
    d.ibd_stats, d.first_verdict = {}, ctx.t_start
    sink = harness.Sink()
    sink.t = [float(i) / 10 for i in range(120)]
    sink.nsigs = [2] * 120
    _, samples = d.end_to_end(sink, _Mark(1.0, 10), _Mark(9.0, 90))
    assert samples["backlog_left_share"] == [pytest.approx(55.0)]
    got = harness.read_per_layer(ctx, harness.Reading({}, 8.0, None, samples, {}))
    assert got["backlog.left_share"]["value"] == pytest.approx(55.0)


def test_a_blocks_rehearsal_reads_its_unsent_share(capfd):
    res = rehearse("bch-32mb.blocks")
    assert res["correct"] is True
    out = capfd.readouterr().out
    rows = {}
    for ln in out.splitlines():
        if ln.startswith('{"line": '):
            row = json.loads(ln)
            rows[row["line"]] = row
    b = rows["blocks"]
    assert b["sent_at_open"] >= 3 + 2 and b["sent_at_close"] > b["sent_at_open"]
    want = 100.0 * (1 - b["sent_at_close"] / b["blocks_made"])
    assert rows["per_layer_untraced"]["backlog.left_share"] == pytest.approx(want)
    assert 0.0 <= want < 100.0


# ---- a backlog that is over before the capture --------------------------------


def test_a_traced_run_whose_backlog_is_over_before_its_capture_says_so(capfd):
    """An ibd rehearsal with a chain a CPU finishes in about a second and a
    capture due at window second 10: the driver closes the window at 95%,
    the harness exits on it, and no result object is printed."""
    short = harness.deep_merge(tiny("ibd"), {
        "backlog": {"holds_to_sigs_per_s": 1, "min_blocks": 30}})
    with pytest.raises(SystemExit) as e:
        rehearse("bch-node.ibd", seconds=14.0, trace=True, traffic=short)
    msg = str(e.value)
    assert msg.startswith("chipbench: bch-node.ibd: the backlog was over at "
                          "window second ")
    assert "before the traced capture begins at window second 10.00 of 14" in msg
    assert "chipbench/traffic/ibd.json" in msg
    assert '"holds_to_sigs_per_s": 1' in msg and '"min_blocks": 30' in msg
    out = capfd.readouterr().out
    assert '"correct"' not in out and '"line": "run"' not in out


class _Over:
    """A driver whose backlog is over the moment the window opens."""

    CONNECT_EARLY = False
    oracle = None

    def __init__(self):
        self.offered = harness.Offered({}, {}, {}, {})
        self.asked = 0

    def remotes(self):
        return []

    def on_verdict(self, txid, now):
        pass

    async def ramp(self, node, sink):
        pass

    def closed_early(self, sink):
        self.asked += 1
        return True

    async def drain(self, node, sink):
        raise AssertionError("the run went on past its empty capture")


def test_the_exit_with_a_stub_driver_and_no_rehearsal_of_a_mix():
    """``harness._run``'s exit alone: untraced, the same stub runs on to its
    drain; traced, it exits before it, with the cell, the two moments and
    the traffic file's section in the line."""
    import gc

    from tpunode.metrics import metrics

    bench, wl, cfg, tr = harness.load_cell("bch-32mb.blocks")
    cfg = harness.deep_merge(cfg, CPU)

    def run(trace: bool):
        ctx = harness.Ctx(wl, bench, cfg, tr, 1, 8.0, trace,
                          harness.Rehearsal(), time.monotonic())
        driver = _Over()

        async def go():
            with harness.run_directory(ctx):
                making = asyncio.ensure_future(asyncio.sleep(0))
                return await harness._run(ctx, driver, making, metrics, gc)

        try:
            return asyncio.run(go()), driver
        finally:
            gc.unfreeze()

    with pytest.raises(AssertionError, match="went on past"):
        run(False)
    with pytest.raises(SystemExit) as e:
        run(True)
    msg = str(e.value)
    assert "bch-32mb.blocks: the backlog was over at window second 0." in msg
    assert "begins at window second 4.00 of 8" in msg
    assert "chipbench/traffic/blocks.json" in msg
    assert '"measured_sigs_per_s": 104813' in msg and "note" not in msg


# ---- the reference is the configuration's ------------------------------------


def test_the_default_reference_is_reference_py():
    from chipbench import reference, reference_utxo

    for wl in BENCH["workloads"]:
        _, _, cfg, _ = harness.load_cell(wl["name"])
        assert "reference" not in cfg  # the files need no edit
        assert harness.reference_module(cfg) is reference
    assert harness.reference_module(
        {"reference": "reference_utxo"}) is reference_utxo


def _checkout_with_another_reference(tmp_path, body: str):
    """A copy of the benchmark with one more configuration, which names a
    reference module of its own, and a cell of it: new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    cb = root / "chipbench"
    cfg = harness.load_json(ROOT, "chipbench", "configs", "bch-node.json")
    cfg["reference"] = "reference_other"
    (cb / "configs" / "other-node.json").write_text(json.dumps(cfg))
    (cb / "reference_other.py").write_text(body)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "other-node", "source": "fixture",
                             "file": "chipbench/configs/other-node.json",
                             "reduced": [], "why": "fixture"})
    bench["workloads"].append({"name": "other-node.mempool",
                               "config": "other-node", "traffic": "mempool",
                               "chips": 1, "why": "fixture"})
    for m in bench["end_to_end"]:
        if m["name"] == "verdict_p50_ms":
            m["workloads"].append("other-node.mempool")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def _run_in(root, code: str):
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join([str(root), ROOT])))


REHEARSE = ("import json; from chipbench.tests.rehearse import rehearse; "
            "print(json.dumps(rehearse('other-node.mempool')))")


def test_a_configuration_that_names_another_reference_is_checked_by_it(tmp_path):
    """The other module agrees with ``reference.py`` and leaves a mark for
    every job it is given: the run is correct, and the marks are there."""
    root, before = _checkout_with_another_reference(tmp_path, (
        "import os\n"
        "from chipbench import reference\n\n\n"
        "def check_job(job):\n"
        "    assert set(job) == {'raw', 'p2pk', 'checks'}, sorted(job)\n"
        "    with open(os.path.join(os.path.dirname(__file__), '..',\n"
        "                           'asked.txt'), 'a') as f:\n"
        "        f.write('%d\\n' % len(job['raw']))\n"
        "    return reference.check_job(job)\n"))
    p = _run_in(root, REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["compared"]["reference_vs_program"] == {"value": 0, "limit": 0}
    asked = [int(n) for n in (root / "asked.txt").read_text().split()]
    assert sum(asked) == 60  # the rehearsal's reference_sample_txs
    assert all(p.read_bytes() == b for p, b in before.items())
    # a cell of a configuration that names none is not served by it
    (root / "asked.txt").unlink()
    p = _run_in(root, REHEARSE.replace("other-node.mempool", "bch-node.mempool"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert not (root / "asked.txt").exists()


def test_another_reference_that_disagrees_reads_not_correct(tmp_path):
    """It is the judge, not a bystander: one that knows no template (None
    for every tx) fails the run on both of its comparisons."""
    root, _ = _checkout_with_another_reference(tmp_path, (
        "from chipbench import reference\n\n\n"
        "def check_job(job):\n"
        "    return [(t, None) for t, _ in reference.check_job(job)]\n"))
    p = _run_in(root, REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["reference_vs_program"]["value"] == 60
    assert res["compared"]["reference_vs_construction"]["value"] == 60
    assert "compared reference_vs_program = 60 (limit 0)" in p.stderr


def test_the_controls_still_read_not_correct_through_the_chosen_reference():
    """``control.py`` puts the weakened reference in the program's place
    through ``harness.run_reference``: every control misjudges something."""
    from chipbench import control

    row = asyncio.run(control.one_seed("bch-node.mempool", 5, 0.2))
    assert row["compared"] > row["invalid_txs"] > 0
    for name in control.CONTROLS:
        assert row[name] > 0, (name, row)
