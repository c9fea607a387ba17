"""The block connect that takes the extractor's delta blob as it is
(ISSUE 26): ``UtxoStore.apply_ops_blob`` -> ``store.write_delta`` ->
``LogKV.write_delta`` against the reference ``apply_block`` on the same
blocks — log bytes, index, accounting, undo record and watermark
identical; a malformed blob refused before a byte is written; and the
store's chaos points on the new write, beside the old one.
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys

import pytest

from chipbench import gen
from chipbench import wirefmt as w
from tpunode.chaos import CRASH_EXIT, ChaosFault, ChaosPlan, chaos
from tpunode.metrics import metrics
from tpunode.store import LogKV, MemoryKV, Namespaced
from tpunode.utxo import UtxoStore
from tpunode.wire import LazyBlock

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "chipbench", "traffic", "blocks.json")) as _f:
    MIX = json.load(_f)["mix"]  # bch-32mb.blocks' own
ENTRIES = ("apply_block", "apply_ops_blob")


@pytest.fixture
def chaos_off():
    yield
    chaos.uninstall()


def mix_block(n_txs: int, height: int, seed: int = 26):
    """(tx region, tx count): a coinbase and ``n_txs`` txs of the cell's mix."""
    raws = []
    if n_txs:
        raws = gen.gen_job(gen.jobs_for(MIX, seed, n_txs, n_txs)[0])["raw"]
    return w.coinbase(height) + b"".join(raws), n_txs + 1, raws


def spending_block(parent_raws: list, height: int):
    """A block whose txs each spend output 0 of one tx of ``parent_raws``
    (and one prevout the store never saw): its undo record carries
    pre-spend values."""
    raws = [
        w.ser_tx(2, [(w.sha256d(p), 0, b"\x51", 0xFFFFFFFF),
                     (bytes([i % 251]) * 32, 1, b"", 0xFFFFFFFF)],
                 [(1000 + i, b"\x76\xa9" + bytes([i % 256]) * (i % 40))])
        for i, p in enumerate(parent_raws)
    ]
    return w.coinbase(height) + b"".join(raws), len(raws) + 1, raws


# case -> the chain of (region, count) it connects, last block the one compared
def _chain(case: str) -> list:
    if case == "mix-2000":
        return [mix_block(2000, 1)[:2]]
    if case == "mix-64":
        return [mix_block(64, 1)[:2]]
    if case == "coinbase-only":
        return [mix_block(0, 1)[:2]]
    first = mix_block(64, 1)
    return [first[:2], spending_block(first[2], 2)[:2]]


def connect(u: UtxoStore, entry: str, height: int, region: bytes, count: int):
    block_hash = w.sha256d(b"block %d" % height)
    if entry == "apply_block":
        return u.apply_block(
            height, block_hash, list(LazyBlock(None, count, region).txs)
        )
    with txextract.ParsedTxRegion(region, count) as parsed:
        ops = parsed.utxo_ops()
    return u.apply_ops_blob(height, block_hash, *ops)


def log_bytes(dirpath) -> dict:
    return {name: open(os.path.join(dirpath, name), "rb").read()
            for name in sorted(os.listdir(dirpath))}


def state(s: LogKV) -> tuple:
    return dict(s._data), s._live_bytes, s._dead_bytes


@pytest.mark.parametrize(
    "case", ["mix-2000", "mix-64", "coinbase-only", "spends-the-first"])
def test_delta_connect_is_apply_block_to_the_byte(tmp_path, case):
    chain = _chain(case)
    stores = {}
    for entry in ENTRIES:
        d = tmp_path / entry
        s = LogKV(str(d / "kv.log"))
        u = UtxoStore(Namespaced(s, b"u/"))
        prior = None
        for height, (region, count) in enumerate(chain, start=1):
            prior = (state(s), u.height, u.block_hash)
            assert connect(u, entry, height, region, count)
        stores[entry] = (s, u, str(d), prior)
    sa, ua, da, _ = stores["apply_block"]
    sb, ub, db, prior = stores["apply_ops_blob"]
    tip = len(chain)
    assert log_bytes(da) == log_bytes(db)
    assert state(sa) == state(sb)
    undo_key = b"u/U" + struct.pack("<q", tip)
    assert sb._data[undo_key] == sa._data[undo_key]
    assert (ub.height, ub.block_hash) == (ua.height, ua.block_hash) == (
        tip, w.sha256d(b"block %d" % tip))
    assert sb._data[b"u/!wm"] == struct.pack("<q", tip) + ub.block_hash
    if case == "spends-the-first":
        # pre-spend values in the record: 64 spent outputs of block 1
        n_spent = struct.unpack_from("<I", sb._data[undo_key], 8 + 4 + 32)[0]
        assert n_spent == 64
    # disconnect() after the new path: the prior state exactly
    assert ub.disconnect()
    assert (dict(sb._data), ub.height, ub.block_hash) == (
        prior[0][0], prior[1], prior[2])
    assert ua.disconnect() and state(sa) == state(sb)
    # and the log replays to what the index holds
    sb.close()
    reopened = LogKV(os.path.join(db, "kv.log"))
    assert dict(reopened._data) == dict(sb._data)
    reopened.close()
    sa.close()


def test_delta_connect_through_the_group_commit_writer(tmp_path):
    """The node's chain store starts LogKV's writer thread; a delta then
    appends directly under the lock — same bytes, same index."""
    from tpunode.store import put_op

    region, count = mix_block(64, 1)[:2]
    out = {}
    for entry in ENTRIES:
        s = LogKV(str(tmp_path / entry / "kv.log"))
        s.write_batch_async([put_op(b"\x90h", b"header")]).result(10)
        connect(UtxoStore(Namespaced(s, b"u/")), entry, 1, region, count)
        s.close()
        out[entry] = (log_bytes(str(tmp_path / entry)), state(s))
    assert out["apply_block"] == out["apply_ops_blob"]


def _v1(op: int, key: bytes, value: bytes = b"") -> bytes:
    return struct.pack("<BII", op, len(key), len(value)) + key + value


GOOD = _v1(1, b"o" + b"\x01" * 36, b"\x05" * 12) + _v1(2, b"o" + b"\x02" * 36)
BAD_BLOBS = {
    "bad-opcode": _v1(1, b"oa", b"v") + _v1(3, b"ob"),
    "length-past-the-end": GOOD[:-5],
    "short-header": GOOD + b"\x01\x02",
    "put-after-delete": _v1(2, b"oa") + _v1(1, b"ob", b"v"),
    "delete-with-a-value": _v1(1, b"oa", b"v") + _v1(2, b"ob", b"v"),
}


@pytest.mark.parametrize("engine", ["log", "memory"])
@pytest.mark.parametrize("bad", sorted(BAD_BLOBS))
def test_malformed_blob_is_refused_before_any_byte(tmp_path, engine, bad):
    s = LogKV(str(tmp_path / "kv.log")) if engine == "log" else MemoryKV()
    u = UtxoStore(Namespaced(s, b"u/"))
    assert u.apply_ops_blob(1, b"\x11" * 32, GOOD, 1, 1)
    before = (dict(s._data), log_bytes(str(tmp_path)))
    with pytest.raises(ValueError):
        u.apply_ops_blob(2, b"\x22" * 32, BAD_BLOBS[bad], 1, 1)
    assert (dict(s._data), log_bytes(str(tmp_path))) == before
    assert u.height == 1 and u.block_hash == b"\x11" * 32
    assert u.apply_ops_blob(2, b"\x22" * 32, GOOD, 1, 1)  # still writable
    s.close()


@pytest.mark.parametrize("framer", ["native", "decoded"])
def test_write_delta_is_write_batch_of_the_decoded_ops(
        tmp_path, monkeypatch, framer):
    """Store level, on a delta no block would make: a key put twice, a key
    that was there before, an empty value, keys of several lengths, a
    delete of a key the same blob puts, of one that is not there, of one
    key twice — ``write_delta`` leaves the log, the index and the
    accounting that ``write_batch`` of the same ops leaves, with the
    native framer and without it."""
    from tpunode import store as store_mod
    from tpunode.store import put_op

    if framer == "decoded":
        monkeypatch.setattr(store_mod, "_delta_framer_state", (None,))
    blob = b"".join([
        _v1(1, b"dup", b"first"), _v1(1, b"was-there", b"new value"),
        _v1(1, b"dup", b"second, longer"), _v1(1, b"empty"), _v1(1, b"k", b"v"),
        _v1(2, b"k"), _v1(2, b"absent"), _v1(2, b"was-there-too"),
        _v1(2, b"was-there-too"),
    ])
    seen = []

    def tail(put_keys, del_keys, del_olds, strip):
        seen.append((put_keys, del_keys, del_olds, strip))
        return [put_op(b"closing", b"op")]

    stores = []
    for how in ("write_batch", "write_delta"):
        s = LogKV(str(tmp_path / how / "kv.log"))
        view = Namespaced(s, b"n/")
        view.write_batch([put_op(b"was-there", b"old"),
                          put_op(b"was-there-too", b"old too")])
        if how == "write_delta":
            view.write_delta(blob, tail)
        else:
            ops, n_puts = store_mod._decode_delta(blob)
            assert n_puts == 5 and len(ops) == 9
            view.write_batch(ops + [put_op(b"closing", b"op")])
        stores.append((log_bytes(str(tmp_path / how)), state(s)))
        s.close()
    assert stores[0] == stores[1]
    assert stores[1][1][0][b"n/dup"] == b"second, longer"
    # the tail saw the store's keys, and what the deletes would remove
    # as it stood before the batch
    (put_keys, del_keys, del_olds, strip), = seen
    assert strip == 2 and put_keys[0] == b"n/dup" and len(put_keys) == 5
    assert del_keys == [b"n/k", b"n/absent", b"n/was-there-too",
                        b"n/was-there-too"]
    assert del_olds == [None, None, b"old too", b"old too"]


# ---------------------------------------------------------------------------
# the store's chaos points, on the old write and on the new

def _two_blocks():
    first = mix_block(64, 1)
    return [first[:2], spending_block(first[2], 2)[:2]]


def _faultless(tmp_path) -> tuple:
    """(state after block 1, state after block 2, log after block 2)."""
    d = tmp_path / "faultless"
    s = LogKV(str(d / "kv.log"), fsync=True)
    u = UtxoStore(Namespaced(s, b"u/"))
    blocks = _two_blocks()
    connect(u, "apply_ops_blob", 1, *blocks[0])
    one = dict(s._data)
    connect(u, "apply_ops_blob", 2, *blocks[1])
    s.close()
    return one, dict(s._data), log_bytes(str(d))["kv.log.00000001.seg"]


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("point", ["store.write", "store.append"])
def test_injected_error_applies_none_of_the_block(
        tmp_path, chaos_off, entry, point):
    one, two, _ = _faultless(tmp_path)
    d = tmp_path / "run"
    s = LogKV(str(d / "kv.log"), fsync=True)
    u = UtxoStore(Namespaced(s, b"u/"))
    blocks = _two_blocks()
    connect(u, entry, 1, *blocks[0])
    disk = log_bytes(str(d))
    chaos.install(ChaosPlan.parse(f"seed=26;{point}:error:n=1"))
    with pytest.raises(ChaosFault):
        connect(u, entry, 2, *blocks[1])
    chaos.uninstall()
    # none of it: not in the index, not on the disk, watermark unmoved
    assert dict(s._data) == one and log_bytes(str(d)) == disk
    assert u.height == 1
    assert connect(u, entry, 2, *blocks[1])  # the re-delivery connects
    assert dict(s._data) == two
    s.close()
    reopened = LogKV(str(d / "kv.log"))
    assert dict(reopened._data) == two
    reopened.close()


def _child(dirpath: str, entry: str) -> None:
    """Subprocess body (plan via TPUNODE_CHAOS, armed at import): connect
    the two blocks on a fsynced store; the fault fires on the second
    block's append."""
    s = LogKV(os.path.join(dirpath, "kv.log"), fsync=True)
    u = UtxoStore(Namespaced(s, b"u/"))
    for height, (region, count) in enumerate(_two_blocks(), start=1):
        connect(u, entry, height, region, count)
        with open(os.path.join(dirpath, "acked"), "a") as f:
            f.write(f"{height}\n")
    s.close()


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("action", ["crash", "torn_write", "bit_flip"])
def test_damaged_append_leaves_the_whole_block_or_none(
        tmp_path, entry, action):
    one, two, good_log = _faultless(tmp_path)
    d = tmp_path / "run"
    d.mkdir()
    env = dict(os.environ,
               TPUNODE_CHAOS=f"seed=26;store.append:{action}:after=1,n=1")
    env.pop("TPUNODE_EVENTS", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         "from tests.test_utxo_delta import _child; "
         f"_child({str(d)!r}, {entry!r})"],
        cwd=REPO, env=env, capture_output=True, timeout=120,
    )
    # a flipped bit is written and acked (media damage); the others die
    # before the block is acked
    want_rc, acked = (
        (0, "1\n2\n") if action == "bit_flip" else (CRASH_EXIT, "1\n"))
    assert proc.returncode == want_rc, proc.stderr.decode()[-500:]
    assert open(d / "acked").read() == acked
    on_disk = log_bytes(str(d))["kv.log.00000001.seg"]
    assert len(on_disk) <= len(good_log)
    if action != "bit_flip":  # what reached the disk is what was meant to
        assert good_log.startswith(on_disk)
    corrupt0 = metrics.get("store.corruption")
    s = LogKV(str(d / "kv.log"), fsync=True)
    u = UtxoStore(Namespaced(s, b"u/"))
    loud = metrics.get("store.corruption") - corrupt0
    # the watermark is the batch's last record: whatever was lost or
    # damaged, block 2 is not connected — and nothing that is not block
    # 1's or block 2's own value is ever served
    assert u.height == 1
    for k, v in s._data.items():
        assert v in (one.get(k), two.get(k)), k
    if action == "crash":
        assert dict(s._data) == one and not loud
    if action == "bit_flip":
        assert loud == 1
    # the re-delivered block heals whatever prefix of the batch replayed
    assert connect(u, entry, 2, *_two_blocks()[1])
    assert u.height == 2
    assert {k: v for k, v in s._data.items() if k[2:3] == b"o"} == {
        k: v for k, v in two.items() if k[2:3] == b"o"}
    s.close()


# ---------------------------------------------------------------------------
# in the node: one parse a block, one ``utxo.connect`` span a connect

@pytest.mark.asyncio
@pytest.mark.parametrize("case", ["sharded", "small", "reference"])
async def test_utxo_connect_span_once_a_block_from_a_worker(monkeypatch, case):
    """``reference``: a node with no engine — the delta comes out of a
    parse in the connect's worker — and every record of its UTXO namespace
    is ``UtxoStore.apply_block``'s on a ``MemoryKV``."""
    import threading

    from benchmarks.txgen import gen_chain
    from tests.fakenet import poll_until
    from tests.fixtures import all_blocks, reference_set, utxo_records
    from tests.test_ibd import NET, ibd_node
    from tpunode import trace

    if case == "sharded":  # 151 txs a block: four range jobs each
        blocks = gen_chain(NET, 2, 150, seed=0x1BD2, cache="ibd_t_2x150.bin",
                           mix=True)
    else:
        blocks = all_blocks()
    seen, parses = [], []

    def watched(name):
        inner = getattr(UtxoStore, name)

        def call(self, *a, **kw):
            seen.append((
                name, threading.current_thread() is threading.main_thread(),
                sum(sp._name == "utxo.connect" for sp in list(trace._open)),
            ))
            return inner(self, *a, **kw)

        monkeypatch.setattr(UtxoStore, name, call)

    watched("apply_ops_blob")
    watched("apply_block")
    parse = txextract.ParsedTxRegion.__init__
    monkeypatch.setattr(
        txextract.ParsedTxRegion, "__init__",
        lambda self, data, n=-1: (parses.append(n), parse(self, data, n))[1])
    keys = ("span.utxo.connect.count", "span.utxo.connect.seconds",
            "span.utxo.connect.cpu_seconds", "utxo.applied")
    before = [metrics.get(k) for k in keys]
    async with ibd_node(MemoryKV(), blocks, verify=case != "reference",
                        extract_workers=4) as (node, _events):
        await poll_until(lambda: node.utxo.height == len(blocks), timeout=60,
                         what=f"utxo catch-up ({case})")
        records = utxo_records(node)
    count, seconds, cpu, applied = (
        metrics.get(k) - b for k, b in zip(keys, before))
    assert count == applied == len(blocks) == len(seen)
    # worker thread, inside the span
    assert set(seen) == {("apply_ops_blob", False, 1)}
    assert 0.0 <= cpu <= seconds + 0.02  # the clocks' ticks differ
    assert seconds > 0.0
    # one parse a block: the one verification made, or the connect's own
    assert sorted(parses) == sorted(len(b.txs) for b in blocks)
    if case == "reference":
        assert records == reference_set(blocks)
