"""The ``open`` driver and its two cells (``bch-tip.tip``,
``bch-node.relay-open``) at a tiny size on the CPU: both read ``correct:
true``; a reuse that answers what it should verify reads ``correct:
false``; the schedule is the seed's; the generator's lateness is reported.

The tiny sizes are the traffic files' ``rehearsal`` sections."""

import time

import pytest

from chipbench import harness
from chipbench.drivers import open as open_driver
from chipbench.tests.rehearse import rehearse, tiny

TIP, RELAY_OPEN = "bch-tip.tip", "bch-node.relay-open"


def _lines(capfd, kind: str) -> list:
    import json

    out = []
    for row in capfd.readouterr().out.splitlines():
        if row.startswith('{"line": "' + kind + '"'):
            out.append(json.loads(row))
    return out


def test_the_tip_cell_is_answered_from_relay_verdicts_and_is_correct(capfd):
    res = rehearse(TIP, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 85.0 <= m["reuse.hit_share"] <= 100.0
    assert m["reuse.ms_per_block"] > 0 and m["reuse.cpu_ms_per_block"] > 0
    assert m["reuse.cpu_ms_per_block"] <= m["reuse.ms_per_block"] * 1.5 + 1
    assert m["tip.block_verdict_p50_ms"] > 0
    assert m["tip.relay_verdict_p50_ms"] > 0 and m["open.late_p99_ms"] >= 0
    assert m["utxo.connect_ms_per_block"] > 0
    (line,) = _lines(capfd, "open")
    assert line["blocks_in_window"] >= 3 and line["blocks_sent"] >= 4
    assert line["reuse"]["node.reuse_hits"] > 0
    assert line["late_ms"]["n"] > 0 and line["block_ms"]["n"] >= 3


def test_the_open_relay_cell_reports_its_end_to_end_metrics(capfd):
    res = rehearse(RELAY_OPEN)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"sigs_per_s", "verdict_p50_ms",
                                   "host_cpu_ms_per_ksig", "setup_s"}
    (line,) = _lines(capfd, "open")
    assert line["blocks_sent"] == 0 and line["late_ms"]["n"] > 100
    # below the knee the rate is the offered one
    offered = 500 * 26 / 12  # the mix's 26 signatures per 12 txs
    assert res["metrics"]["sigs_per_s"]["value"] == pytest.approx(offered, rel=0.2)


def test_a_cell_reports_only_the_end_to_end_metrics_its_traffic_lists():
    res = rehearse(TIP, traffic=dict(tiny("tip"), end_to_end=[
        "sigs_per_s", "host_cpu_ms_per_ksig"]))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"sigs_per_s", "host_cpu_ms_per_ksig",
                                   "setup_s"}


def _answers(everything: bool):
    """A reuse gone wrong in the program's place: every transaction it
    looks up and has seen (``everything``: or has not) comes back valid."""
    from tpunode.mempool import Mempool
    from tpunode.txverify import ExtractStats

    plain = Mempool.relay_verdicts

    def broken(self, keys):
        hits, pending, unfit = plain(self, keys)
        out = {i: (True, tuple(True for _ in v), st)
               for i, (_, v, st) in hits.items()}
        if everything:
            for i in range(len(keys)):
                out.setdefault(i, (True, (), ExtractStats()))
        return out, pending, unfit

    return broken


@pytest.mark.parametrize("everything", [False, True],
                         ids=["known-as-valid", "unseen-too"])
def test_a_reuse_that_answers_what_it_should_verify_reads_not_correct(
        everything, monkeypatch, capfd):
    """The relayed adversarial txs come back in blocks, and the unseen ones
    carry adversarial txs of their own: both controls are seen."""
    from tpunode.mempool import Mempool

    monkeypatch.setattr(Mempool, "relay_verdicts", _answers(everything))
    res = rehearse(TIP)
    assert res["correct"] is False and res["failed"] > 0
    wrong = {c["name"]: c["value"] for c in _lines(capfd, "compared")}
    assert wrong["verdicts_differing_from_construction"] > 0
    assert wrong["verdicts_missing"] == 0


def _driver(cell: str, seed: int, seconds: float = 3.0):
    bench, wl, cfg, tr = harness.load_cell(cell)
    tr = harness.deep_merge(tr, tiny(wl["traffic"]))
    ctx = harness.Ctx(wl, bench, cfg, tr, seed, seconds, False,
                      harness.Rehearsal(), time.monotonic())
    return open_driver.Driver(ctx)


@pytest.mark.parametrize("cell", [TIP, RELAY_OPEN])
def test_the_schedule_is_the_seeds(cell):
    import asyncio

    async def make():
        a, b, c = (_driver(cell, s) for s in (2**31 + 5, 2**31 + 5, 11))
        return a, b, c

    a, b, c = asyncio.run(make())
    assert a.due == b.due and a.peer_of == b.peer_of and a.known == b.known
    assert a.due != c.due
    # the same counts for every seed; the seed moves the moments
    assert a.n_txs == c.n_txs and a.n_blocks == c.n_blocks
    assert a.due == sorted(a.due) and a.due[0] > 0
    rate = a.n_txs / a.length
    assert rate == pytest.approx(a.ctx.traffic["txs_per_s"], rel=0.02)
    assert set(a.peer_of) == set(range(a.ctx.traffic["peers"]))
    if cell == TIP:
        t = a.ctx.traffic
        assert a.n_blocks == int(a.length / t["block_every_s"])
        for k, ((lo, hi), n_un) in enumerate(zip(a.known, a.n_unseen), 1):
            assert n_un == (hi - lo) // 19
            assert all(t["block_every_s"] * (k - 1) - t["known_lag_s"]
                       <= a.due[i] < t["block_every_s"] * k - t["known_lag_s"]
                       for i in range(lo, hi))
        # every relay tx due before the last block's cut is in one block
        assert [lo for lo, _ in a.known[1:]] == [hi for _, hi in a.known[:-1]]
    else:
        assert a.n_blocks == 0 and a.block_peer is None
