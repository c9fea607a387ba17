import os

import pytest

from tpunode.store import (
    LogKV, MemoryKV, Namespaced, delete_op, get_many, open_store, put_op,
)


def _native(path):
    from tpunode.native import NativeKV

    try:
        return NativeKV(path)
    except Exception as e:
        pytest.skip(f"native kvstore unavailable: {e}")


@pytest.fixture(params=["memory", "log", "native"])
def kv(request, tmp_path):
    if request.param == "memory":
        s = MemoryKV()
    elif request.param == "log":
        s = LogKV(str(tmp_path / "kv.log"))
    else:
        s = _native(str(tmp_path / "kv.log"))
    yield s
    s.close()


def test_basic_ops(kv):
    assert kv.get(b"a") is None
    kv.put(b"a", b"1")
    kv.put(b"b", b"2")
    assert kv.get(b"a") == b"1"
    kv.delete(b"a")
    assert kv.get(b"a") is None
    assert kv.get(b"b") == b"2"


def test_write_batch_and_scan(kv):
    kv.write_batch(
        [
            put_op(b"\x90aa", b"1"),
            put_op(b"\x90ab", b"2"),
            put_op(b"\x91xx", b"3"),
            delete_op(b"\x90aa"),
        ]
    )
    assert dict(kv.scan_prefix(b"\x90")) == {b"\x90ab": b"2"}
    assert dict(kv.scan_prefix(b"\x91")) == {b"\x91xx": b"3"}


def test_log_store_durability(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"k1", b"v1")
    s.put(b"k2", b"v2")
    s.delete(b"k1")
    s.put(b"k2", b"v2b")  # overwrite
    s.close()
    s2 = LogKV(path)
    assert s2.get(b"k1") is None
    assert s2.get(b"k2") == b"v2b"
    s2.close()


def test_log_store_torn_tail(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"good", b"yes")
    s.close()
    with open(path, "ab") as f:
        f.write(b"\x01\x05\x00")  # half a record header
    s2 = LogKV(path)
    assert s2.get(b"good") == b"yes"
    # and the torn tail was truncated so appends stay valid
    s2.put(b"more", b"data")
    s2.close()
    s3 = LogKV(path)
    assert s3.get(b"more") == b"data"
    s3.close()


def test_log_store_compaction(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    for i in range(2000):
        s.put(b"hot", b"x" * 2048)  # rewrites same key: garbage accrues
    s.put(b"cold", b"keep")
    s.compact()
    assert os.path.getsize(path) < 3 * 4096
    s.close()
    s2 = LogKV(path)
    assert s2.get(b"hot") == b"x" * 2048
    assert s2.get(b"cold") == b"keep"
    s2.close()


def test_namespaced_views(kv):
    a = Namespaced(kv, b"A:")
    b = Namespaced(kv, b"B:")
    a.put(b"k", b"from-a")
    b.put(b"k", b"from-b")
    assert a.get(b"k") == b"from-a"
    assert b.get(b"k") == b"from-b"
    assert dict(a.scan_prefix(b"")) == {b"k": b"from-a"}
    a.write_batch([delete_op(b"k")])
    assert a.get(b"k") is None
    assert b.get(b"k") == b"from-b"


@pytest.mark.parametrize("view", ["plain", "namespaced", "nested"])
def test_get_many_reads_what_get_reads_key_by_key(kv, view):
    """The batch read (ISSUE 30): ``get_many(kv, keys, prefix)`` is
    ``[kv.get(prefix + k) for k in keys]`` on every engine — LogKV's own
    (one read of the index a key), the ``get``-a-key fallback of the
    others — hits, misses, deleted keys, a repeated key, and the namespace
    joined to the prefix once through one view or two."""
    store = {"plain": kv, "namespaced": Namespaced(kv, b"u/"),
             "nested": Namespaced(Namespaced(kv, b"a/"), b"b/")}[view]
    kv.put(b"ox", b"outside every namespace")
    for k in range(40):
        store.put(b"o" + bytes([k]) * 36, b"v%d" % k)
        store.put(b"p" + bytes([k]) * 36, b"other prefix")
    store.write_batch([delete_op(b"o" + bytes([k]) * 36) for k in (3, 17)])
    tails = [bytes([k]) * 36 for k in (0, 3, 5, 17, 39, 40, 5, 200)] + [b"x"]
    got = get_many(store, tails, b"o")
    assert got == [store.get(b"o" + t) for t in tails]
    assert got == [b"v0", None, b"v5", None, b"v39", None, b"v5", None,
                   b"outside every namespace" if view == "plain" else None]
    assert get_many(store, [b"o" + t for t in tails]) == got  # no prefix
    assert get_many(store, [], b"o") == []
    assert get_many(store, iter(tails[:2]), b"o") == got[:2]  # any iterable
    # a write between two reads is seen by the second
    store.put(b"o" + bytes([3]) * 36, b"back")
    assert get_many(store, tails[1:2], b"o") == [b"back"]


def test_logkv_batch_read_leaves_the_single_reads_histogram_alone(tmp_path):
    kv = LogKV(str(tmp_path / "kv.log"))
    try:
        kv.put(b"oa", b"1")
        def samples() -> int:
            hist = metrics.histogram("store.read_seconds")
            return hist.count if hist is not None else 0

        n0, tick0 = samples(), kv._read_tick
        assert kv.get_many([b"a", b"b", b"c"] * 64, b"o") == [b"1", None, None] * 64
        assert kv.get_many([], b"o") == []
        # store.read_seconds stays one population: the 1-in-64 single reads
        assert samples() == n0 and kv._read_tick == tick0
    finally:
        kv.close()


def test_open_store_dispatch(tmp_path):
    m = open_store(None)
    assert isinstance(m, MemoryKV)
    d = open_store(str(tmp_path / "x.log"), engine="log")
    assert isinstance(d, LogKV)
    d.close()


def test_native_durability_and_torn_tail(tmp_path):
    path = str(tmp_path / "native.log")
    s = _native(path)
    s.put(b"k1", b"v1")
    s.put(b"k2", b"v2")
    s.delete(b"k1")
    s.close()
    with open(path, "ab") as f:
        f.write(b"\x01\x05\x00")  # torn record header
    s2 = _native(path)
    assert s2.get(b"k1") is None
    assert s2.get(b"k2") == b"v2"
    assert s2.count() == 1
    s2.put(b"more", b"data")
    s2.close()
    s3 = _native(path)
    assert s3.get(b"more") == b"data"
    s3.close()


def test_native_compaction(tmp_path):
    path = str(tmp_path / "native.log")
    s = _native(path)
    for _ in range(2000):
        s.put(b"hot", b"x" * 2048)
    s.put(b"cold", b"keep")
    s.compact()
    assert os.path.getsize(path) < 3 * 4096
    assert s.get(b"hot") == b"x" * 2048
    assert s.get(b"cold") == b"keep"
    s.close()


def test_native_v1_replays_bit_identically_under_v2_reader(tmp_path):
    """ISSUE 9 compat pin: a v1 log written by the C++ engine replays to
    the exact same key/value state under the v2 LogKV reader."""
    path = str(tmp_path / "shared.log")
    n = _native(path)
    n.write_batch([put_op(b"\x90aa", b"1"), put_op(b"\x91bb", b"2"),
                   delete_op(b"\x90aa"), put_op(b"\x90ac", b"3")])
    n.put(b"\x92cc", b"4")
    expected = dict(n.scan_prefix(b""))
    n.close()
    s = LogKV(path)
    assert dict(s.scan_prefix(b"")) == expected
    assert s.get(b"\x90aa") is None
    assert s.get(b"\x92cc") == b"4"
    s.close()


def test_native_opens_v2_directory(tmp_path):
    """ISSUE 11: the native engine now replays the v2 segmented format
    (it used to refuse via StoreVersionError); ``auto`` still prefers
    LogKV for v2 directories (async group-commit, quarantining salvage).
    The deep interop matrix lives in tests/test_native_v2.py."""
    path = str(tmp_path / "v2.log")
    s = LogKV(path)
    s.put(b"k", b"v")
    s.close()
    _native(str(tmp_path / "probe.log")).close()  # skips if unbuildable
    nkv = open_store(path, engine="native")
    assert getattr(nkv, "format_v2", False) is True
    assert nkv.get(b"k") == b"v"
    nkv.close()
    # auto keeps picking the Python engine for v2 directories
    auto = open_store(path)
    assert isinstance(auto, LogKV)
    assert auto.get(b"k") == b"v"
    auto.close()


def test_open_store_native_for_existing_v1_log_only(tmp_path):
    from tpunode.native import NativeKV

    _native(str(tmp_path / "probe.log")).close()  # skips if unbuildable
    # an existing v1 single-file log keeps its native engine under auto
    v1 = str(tmp_path / "v1.log")
    n = _native(v1)
    n.put(b"x", b"y")
    n.close()
    s = open_store(v1)
    assert isinstance(s, NativeKV)
    assert s.get(b"x") == b"y"
    s.close()
    # a fresh path gets the crash-consistent v2 LogKV
    fresh = open_store(str(tmp_path / "fresh.log"))
    assert isinstance(fresh, LogKV)
    fresh.close()


# ---------------------------------------------------------------------------
# log format v2 (ISSUE 9): CRC + seq + segments + salvage + group commit

import struct as _struct

from tpunode.chaos import ChaosFault, ChaosPlan, chaos
from tpunode.events import events
from tpunode.metrics import metrics


@pytest.fixture
def chaos_off():
    yield
    chaos.uninstall()


def _mk_v1(path, records):
    """Handcraft a legacy v1 log: (op, key, value) triples."""
    rec = _struct.Struct("<BII")
    with open(path, "wb") as f:
        for op, k, v in records:
            f.write(rec.pack(op, len(k), len(v)) + k + v)


def test_v1_file_replays_bit_identically(tmp_path):
    """The v2 reader's v1 path, independent of the native toolchain."""
    path = str(tmp_path / "v1.log")
    _mk_v1(path, [(1, b"a", b"xy"), (1, b"b", b"z"), (2, b"a", b""),
                  (1, b"c", b"\x00" * 40)])
    s = LogKV(path)
    assert dict(s.scan_prefix(b"")) == {b"b": b"z", b"c": b"\x00" * 40}
    # new writes land in v2 segments; the v1 base is never appended to
    v1_size = os.path.getsize(path)
    s.put(b"new", b"val")
    assert os.path.getsize(path) == v1_size
    s.close()
    s2 = LogKV(path)
    assert s2.get(b"new") == b"val"
    assert s2.get(b"b") == b"z"
    s2.close()


def test_v2_torn_tail_is_quiet_and_truncated(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"good", b"yes")
    seg = s._file.name
    s.close()
    with open(seg, "ab") as f:
        f.write(b"\x01\x02\x03")  # torn partial record header
    c0 = events.counts().get("store.corruption", 0)
    s2 = LogKV(path)
    assert s2.get(b"good") == b"yes"
    # quiet: a torn tail is NOT corruption (no event), and appends resume
    assert events.counts().get("store.corruption", 0) == c0
    s2.put(b"more", b"data")
    s2.close()
    s3 = LogKV(path)
    assert s3.get(b"more") == b"data"
    s3.close()


def test_v2_midlog_corruption_is_loud_and_salvaged(tmp_path):
    """A flipped bit in a SEALED segment: store.corruption event+metric,
    the corrupt suffix is quarantined, corrupt bytes are never returned,
    and later segments' records survive."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path, segment_bytes=300)
    for i in range(24):
        s.put(f"k{i}".encode(), b"v" * 32)
    segs = sorted(
        p for p in os.listdir(tmp_path) if p.endswith(".seg")
    )
    assert len(segs) >= 3  # rotation actually happened
    s.close()
    target = str(tmp_path / segs[0])
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0x10  # mid-segment damage
    open(target, "wb").write(bytes(blob))
    m0 = metrics.get("store.corruption")
    c0 = events.counts().get("store.corruption", 0)
    s2 = LogKV(path)
    assert metrics.get("store.corruption") == m0 + 1
    assert events.counts().get("store.corruption", 0) == c0 + 1
    assert any("quarantine" in p for p in os.listdir(tmp_path))
    # never corrupt bytes as data: every surviving value is intact
    for k, v in s2.scan_prefix(b"k"):
        assert v == b"v" * 32, (k, v)
    # records from LATER segments survived the salvage
    assert s2.get(b"k23") == b"v" * 32
    s2.close()


def test_v2_sequence_break_detected(tmp_path):
    """A dropped record (valid CRCs, broken seq chain) is corruption, not
    silent data loss."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    for i in range(6):
        s.put(f"k{i}".encode(), b"x" * 8)
    seg = s._file.name
    s.close()
    raw = open(seg, "rb").read()
    hdr = 16  # file header
    rec = 4 + _struct.calcsize("<IBII") + 2 + 8  # one record
    # excise the second record: seq chain now 0, 2, 3...
    surgically = raw[: hdr + rec] + raw[hdr + 2 * rec :]
    open(seg, "wb").write(surgically)
    m0 = metrics.get("store.corruption")
    s2 = LogKV(path)
    assert metrics.get("store.corruption") == m0 + 1
    assert s2.get(b"k0") == b"x" * 8  # valid prefix survives
    s2.close()


def test_stale_compact_temp_cleaned_on_open(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"k", b"v")
    s.close()
    stale = path + ".compact"
    open(stale, "wb").write(b"half-written snapshot garbage")
    s2 = LogKV(path)
    assert not os.path.exists(stale)
    assert s2.get(b"k") == b"v"
    s2.close()


def test_compaction_crash_window_replays_idempotently(tmp_path):
    """The worst compaction crash window: the snapshot already replaced
    the base but the subsumed segments were not yet deleted.  Replay
    applies the snapshot then re-applies the segments — same final state."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path, segment_bytes=300)
    for i in range(20):
        s.put(f"k{i % 5}".encode(), f"v{i}".encode() * 8)
    s.delete(b"k4")
    expected = dict(s.scan_prefix(b""))
    # build the snapshot exactly like compact() does, but KEEP the segments
    import shutil

    backup = {
        p: open(str(tmp_path / p), "rb").read()
        for p in os.listdir(tmp_path) if p.endswith(".seg")
    }
    s.compact()
    s.close()
    # resurrect the pre-compaction segments next to the new snapshot
    for name, blob in backup.items():
        open(str(tmp_path / name), "wb").write(blob)
    shutil.rmtree  # (quiet linters: shutil used for clarity of intent)
    m0 = metrics.get("store.corruption")
    s2 = LogKV(path)
    assert dict(s2.scan_prefix(b"")) == expected
    assert metrics.get("store.corruption") == m0  # clean, not corrupt
    s2.close()


def test_rotation_and_reopen_resume_active_segment(tmp_path):
    path = str(tmp_path / "kv.log")
    s = LogKV(path, segment_bytes=250)
    r0 = metrics.get("store.rotations")
    for i in range(12):
        s.put(f"k{i}".encode(), b"z" * 24)
    assert metrics.get("store.rotations") > r0  # threshold actually rotates
    s.put(b"last", b"small")  # ensures the active segment has room
    active = s._file.name
    s.close()
    s2 = LogKV(path, segment_bytes=250)
    # reopen appends to the same active segment (no gratuitous rotation)
    assert s2._file.name == active
    s2.put(b"resumed", b"yes")
    s2.close()
    s3 = LogKV(path)
    assert s3.get(b"resumed") == b"yes"
    assert all(s3.get(f"k{i}".encode()) == b"z" * 24 for i in range(12))
    s3.close()


def test_group_commit_acked_writes_are_durable(tmp_path):
    import concurrent.futures

    path = str(tmp_path / "kv.log")
    s = LogKV(path, fsync=True)
    futs = [
        s.write_batch_async([put_op(f"g{i}".encode(), b"d" * 16)])
        for i in range(32)
    ]
    # read-your-writes before the ack
    assert s.get(b"g0") == b"d" * 16
    concurrent.futures.wait(futs, timeout=30)
    assert all(f.exception() is None for f in futs)
    assert metrics.get("store.group_commits") > 0
    s.close()
    s2 = LogKV(path)
    assert all(s2.get(f"g{i}".encode()) == b"d" * 16 for i in range(32))
    s2.close()


def test_group_commit_failure_poisons_store(tmp_path, chaos_off):
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.write_batch_async([put_op(b"a", b"1")]).result(10)
    chaos.install(ChaosPlan.parse("seed=1;store.append:error:n=1"))
    fut = s.write_batch_async([put_op(b"b", b"2")])
    with pytest.raises(ChaosFault):
        fut.result(10)
    chaos.uninstall()
    with pytest.raises(RuntimeError, match="failed earlier"):
        s.write_batch([put_op(b"c", b"3")])
    s.close()


def test_write_batch_atomic_under_chaos_logkv(tmp_path, chaos_off):
    """ISSUE 9 satellite: a ChaosFault mid-write_batch leaves index and
    log consistent — no half-applied _data mutations observable."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.write_batch([put_op(b"k1", b"old1"), put_op(b"k2", b"old2")])
    before = dict(s.scan_prefix(b""))
    # store.write fires before any effect; store.append fires after the
    # batch is built but before any byte hits the log or the index
    for plan in ("seed=2;store.write:error:n=1",
                 "seed=2;store.append:error:n=1"):
        chaos.install(ChaosPlan.parse(plan))
        with pytest.raises(ChaosFault):
            s.write_batch(
                [put_op(b"k1", b"new1"), delete_op(b"k2"),
                 put_op(b"k3", b"new3")]
            )
        chaos.uninstall()
        assert dict(s.scan_prefix(b"")) == before
    s.close()
    # and the log agrees with the index after reopen
    s2 = LogKV(path)
    assert dict(s2.scan_prefix(b"")) == before
    s2.close()


def test_write_batch_atomic_under_chaos_memorykv(chaos_off):
    kv = MemoryKV()
    kv.write_batch([put_op(b"k1", b"old1")])
    chaos.install(ChaosPlan.parse("seed=3;store.write:error:n=1"))
    with pytest.raises(ChaosFault):
        kv.write_batch([put_op(b"k1", b"new"), put_op(b"k2", b"new")])
    chaos.uninstall()
    assert kv.get(b"k1") == b"old1" and kv.get(b"k2") is None


def test_write_batch_bogus_op_applies_nothing(tmp_path):
    """A typo'd op must not leave the first half of the batch applied."""
    for kv in (MemoryKV(), LogKV(str(tmp_path / "kv.log"))):
        kv.write_batch([put_op(b"a", b"1")])
        with pytest.raises(ValueError):
            kv.write_batch([put_op(b"b", b"2"), ("bogus", b"c", b"3")])
        assert kv.get(b"b") is None
        assert kv.get(b"a") == b"1"
        kv.close()


def test_streamed_replay_handles_values_larger_than_chunk(tmp_path):
    """Replay is bounded-buffer streaming; a value bigger than one read
    chunk must still parse (and the buffer refill logic with it)."""
    import tpunode.store as store_mod

    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    big = bytes(range(256)) * 600  # ~150KB
    s.put(b"big", big)
    s.put(b"small", b"s")
    s.close()
    # shrink the chunk so the big value spans many refills
    orig = store_mod._REPLAY_CHUNK
    store_mod._REPLAY_CHUNK = 4096
    try:
        s2 = LogKV(path)
        assert s2.get(b"big") == big
        assert s2.get(b"small") == b"s"
        s2.close()
    finally:
        store_mod._REPLAY_CHUNK = orig


def test_headerless_husk_segment_is_not_resumed(tmp_path):
    """Review pin: a last segment whose torn header was truncated to zero
    bytes must be rotated past, never appended to — records at offset 0
    of a headerless file would replay as v1 garbage on the next open."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"a", b"1")
    s.close()
    husk = path + ".00000099.seg"
    open(husk, "wb").close()  # 0-byte husk: a crash mid-header-write
    s2 = LogKV(path)
    assert s2._file.name != husk  # rotated past, not resumed
    s2.put(b"b", b"2")
    s2.close()
    s3 = LogKV(path)
    assert s3.get(b"a") == b"1" and s3.get(b"b") == b"2"
    s3.close()


def test_sync_write_batch_via_writer_is_disk_then_index(tmp_path, chaos_off):
    """Review pin: once the group-commit writer is running, a failing
    sync write_batch must not leave never-durable values readable."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.write_batch_async([put_op(b"a", b"1")]).result(10)  # writer starts
    chaos.install(ChaosPlan.parse("seed=9;store.append:error:n=1"))
    with pytest.raises(Exception):
        s.write_batch([put_op(b"b", b"2")])
    chaos.uninstall()
    assert s.get(b"b") is None  # index never ran ahead of the failed disk
    s.close()


def test_length_field_flip_in_active_segment_is_loud(tmp_path):
    """Review pin: a flipped length field mid-ACTIVE-segment makes the
    record 'extend past EOF' — superficially a torn tail, but CRC-valid
    successor records downstream prove it is corruption (a real tear
    leaves nothing after the cut).  The resync scan reclassifies it:
    loud salvage, never a quiet truncate of acked records."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    for i in range(8):
        s.put(f"k{i}".encode(), b"x" * 32)
    seg = s._file.name
    s.close()
    raw = bytearray(open(seg, "rb").read())
    hdr = 16
    rec = 4 + _struct.calcsize("<IBII") + 2 + 32
    # blow up record 2's vlen so it claims to reach past EOF
    vlen_off = hdr + 2 * rec + 4 + 4 + 1 + 4 + 3  # high byte of vlen
    raw[vlen_off] ^= 0x40
    open(seg, "wb").write(bytes(raw))
    m0 = metrics.get("store.corruption")
    s2 = LogKV(path)
    assert metrics.get("store.corruption") == m0 + 1  # LOUD, not quiet
    assert s2.get(b"k0") == b"x" * 32  # valid prefix survives
    assert any("quarantine" in p for p in os.listdir(tmp_path))
    s2.close()


def test_true_torn_tail_stays_quiet_after_resync_scan(tmp_path):
    """The resync scan must not reclassify a REAL torn tail (garbage with
    no valid successor records) as corruption."""
    path = str(tmp_path / "kv.log")
    s = LogKV(path)
    s.put(b"good", b"yes")
    seg = s._file.name
    s.close()
    with open(seg, "ab") as f:
        # a plausible-looking header claiming a huge record, then noise:
        # exactly what a torn multi-record write looks like
        f.write(_struct.pack("<IIBII", 0xDEAD, 1, 1, 4, 1 << 20) + b"no")
    m0 = metrics.get("store.corruption")
    s2 = LogKV(path)
    assert metrics.get("store.corruption") == m0  # quiet truncate
    assert s2.get(b"good") == b"yes"
    s2.put(b"more", b"data")
    s2.close()
    assert LogKV(path).get(b"more") == b"data"


def test_compaction_concurrent_with_group_commit_writes(tmp_path):
    """Review pin: compaction's slow snapshot write runs outside the
    store lock — async writes issued DURING a compaction must all
    survive the segment cleanup and the reopen."""
    import concurrent.futures
    import threading

    path = str(tmp_path / "kv.log")
    s = LogKV(path, segment_bytes=600)
    for i in range(40):
        s.put(f"k{i % 9}".encode(), b"y" * 48)
    futs = []
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            futs.append(
                s.write_batch_async([put_op(b"c%04d" % i, b"live" * 4)])
            )
            i += 1

    t = threading.Thread(target=pump)
    t.start()
    try:
        for _ in range(3):
            s.compact()
    finally:
        stop.set()
        t.join()
    concurrent.futures.wait(futs, timeout=30)
    assert all(f.exception() is None for f in futs)
    n = len(futs)
    s.close()
    s2 = LogKV(path)
    for i in range(n):
        assert s2.get(b"c%04d" % i) == b"live" * 4, i
    assert s2.get(b"k0") == b"y" * 48
    s2.close()
