"""The ``ibd_utxo`` driver's cell (``bch-utxo.ibd-spend``) and
``bch-32mb.single`` at a tiny size on the CPU: both read ``correct: true``;
a snapshot short of one spendable entry, a connect that skips its deletes
and a driver that leaves the prevout callback in each read ``correct:
false``.

The tiny sizes are the traffic files' ``rehearsal`` sections; the snapshot's
is repeated here for the tests that count against it."""

import json

import pytest

from chipbench.drivers import ibd_utxo
from chipbench.tests.rehearse import rehearse, tiny

SPEND, SINGLE = "bch-utxo.ibd-spend", "bch-32mb.single"
# the tiny chain spends 26,496 outpoints: a snapshot a little larger
SMALL_SET = {"node": {"utxo_snapshot": {"entries": 30000}}}


def _compared(capfd) -> dict:
    return {row["name"]: row["value"] for row in map(
        json.loads, (ln for ln in capfd.readouterr().out.splitlines()
                     if ln.startswith('{"line": "compared"')))}


def test_the_node_answers_every_prevout_from_its_snapshot(capfd):
    res = rehearse(SPEND, trace=True, config=SMALL_SET)
    assert res["correct"] is True and res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["utxo.hit_share"] == 100.0 and m["resolve.oracle_share"] == 0.0
    assert m["resolve.missing_share"] == 0.0
    assert m["utxo.lookup_us_per_row"] > 0 and m["utxo.snapshot_load_s"] > 0
    assert m["resolve.us_per_input"] > m["utxo.lookup_us_per_row"]
    assert 0 < m["utxo.entries"] < 30000  # 128 spent, 65 created a block
    assert m["store.rss_mb"] > 0 and m["store.compactions_in_window"] >= 0
    assert m["utxo.connect_ms_per_block"] > 0
    out = _compared(capfd)
    for name in ("prevout_callback_calls", "rows_no_source_answered",
                 "utxo_lookup_misses", "utxo_entries_differ",
                 "utxo_sample_differs", "utxo_watermark_behind_last_verified"):
        assert out[name] == 0


def test_one_block_outstanding_reports_the_blocks_cells_metrics():
    res = rehearse(SINGLE)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"sigs_per_s", "verdict_p50_ms",
                                   "host_cpu_ms_per_ksig", "setup_s"}


def test_a_snapshot_short_of_one_entry_reads_not_correct(monkeypatch, capfd):
    plain = ibd_utxo.Driver.snapshot_columns

    def short(self):
        parts = plain(self)
        outpoints, values = next(parts)  # the bare-P2PK rows: leave those
        yield outpoints, values
        outpoints, values = next(parts)
        outpoints = outpoints.copy()
        outpoints[0, :32] ^= 0xFF  # the entry is there, under another txid
        yield outpoints, values
        yield from parts

    monkeypatch.setattr(ibd_utxo.Driver, "snapshot_columns", short)
    res = rehearse(SPEND, config=SMALL_SET)
    assert res["correct"] is False
    out = _compared(capfd)
    assert out["rows_no_source_answered"] == out["utxo_lookup_misses"] == 1
    assert out["verdicts_differing_from_construction"] == 1


def test_a_connect_that_skips_its_deletes_reads_not_correct(monkeypatch, capfd):
    from tpunode import store
    from tpunode.utxo import UtxoStore

    plain = UtxoStore.apply_ops_blob

    def creates_only(self, height, block_hash, blob, created, spent):
        ops, n_puts = store._decode_delta(blob)
        kept = b"".join(store._REC_V1.pack(1, len(k), len(v)) + k + v
                        for _, k, v in ops[:n_puts])
        return plain(self, height, block_hash, kept, created, 0)

    monkeypatch.setattr(UtxoStore, "apply_ops_blob", creates_only)
    res = rehearse(SPEND, config=SMALL_SET)
    assert res["correct"] is False and res["failed"] == 0  # verdicts are right
    out = _compared(capfd)
    assert out["utxo_entries_differ"] > 0 and out["utxo_sample_differs"] > 0
    assert out["utxo_lookup_misses"] == 0


def test_a_driver_that_leaves_the_callback_in_reads_not_correct(
        monkeypatch, capfd):
    plain = ibd_utxo.Driver.__init__

    def left_in(self, ctx):
        plain(self, ctx)
        self.oracle = self.values

    monkeypatch.setattr(ibd_utxo.Driver, "__init__", left_in)
    res = rehearse(SPEND, config=SMALL_SET)
    assert res["correct"] is False and res["failed"] == 0
    out = _compared(capfd)
    assert out["prevout_callback_calls"] > 0
    assert out["utxo_lookup_misses"] == out["utxo_sample_differs"] == 0


def test_the_driver_ends_at_once_on_a_program_without_load_snapshot(monkeypatch):
    from tpunode.utxo import UtxoStore

    monkeypatch.delattr(UtxoStore, "load_snapshot")
    with pytest.raises(SystemExit, match="no load_snapshot"):
        rehearse(SPEND, config=SMALL_SET)


def test_the_snapshot_is_the_seeds_and_the_generators(monkeypatch):
    """Two drivers of one seed make the same snapshot, another seed another;
    every entry's amount and script are ``gen.synth_amount`` /
    ``gen.synth_script`` of its outpoint (bare P2PK rows: the table's)."""
    import asyncio
    import time

    from chipbench import gen, harness

    def made(seed):
        bench, wl, cfg, tr = harness.load_cell(SPEND)
        cfg = harness.deep_merge(cfg, SMALL_SET)
        tr = harness.deep_merge(tr, tiny("ibd-spend"))
        ctx = harness.Ctx(wl, bench, cfg, tr, seed, 1.0, False,
                          harness.Rehearsal(), time.monotonic())

        async def go():
            harness.start_pool(ctx)
            try:
                d = ibd_utxo.Driver(ctx)
                await d.prepare()
                return d
            finally:
                ctx.pool.terminate()
                ctx.pool.join()

        d = asyncio.run(go())
        rows = []
        for outpoints, values in d.snapshot_columns():
            rows += zip(ibd_utxo._rows(outpoints), ibd_utxo._rows(values))
        return d, rows, b"".join(d.snapshot_batches())

    a, rows_a, blob_a = made(2**31 + 9)
    _, rows_b, blob_b = made(2**31 + 9)
    _, rows_c, _ = made(12)
    assert rows_a == rows_b and blob_a == blob_b and rows_a != rows_c
    assert len(rows_a) == len(rows_c) == 30000 == len(dict(rows_a))
    assert len(blob_a) == sum(9 + 37 + len(v) for _, v in rows_a)
    for key, value in rows_a[::23] + rows_a[-50:]:
        txid, vout = key[:32], int.from_bytes(key[32:], "little")
        assert vout < 4
        assert int.from_bytes(value[:8], "little") == gen.synth_amount(txid, vout)
        assert value[8:] == a.values.p2pk.get(key, gen.synth_script(txid))
    assert any(len(v) == 43 for _, v in rows_a)  # a bare-P2PK row is there
