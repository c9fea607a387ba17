import asyncio
import os
import random
import time

import pytest


from tpunode.metrics import metrics
from tpunode.verify.ecdsa_cpu import CURVE_N, GENERATOR, point_mul, sign
from tpunode.verify.engine import VerifyConfig, VerifyEngine

rng = random.Random(4242)


def make_items(count, tamper_every=0):
    items, expected = [], []
    for i in range(count):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        r, s = sign(priv, z, rng.getrandbits(256))
        if tamper_every and i % tamper_every == 0:
            z ^= 1
            expected.append(False)
        else:
            expected.append(True)
        items.append((pub, z, r, s))
    return items, expected


@pytest.mark.asyncio
async def test_engine_cpu_backend():
    items, expected = make_items(12, tamper_every=4)
    async with VerifyEngine(VerifyConfig(backend="cpu", max_wait=0.0)) as eng:
        got = await eng.verify(items)
    assert got == expected


@pytest.mark.asyncio
async def test_engine_oracle_backend():
    items, expected = make_items(4, tamper_every=2)
    async with VerifyEngine(VerifyConfig(backend="oracle", max_wait=0.0)) as eng:
        got = await eng.verify(items)
    assert got == expected


@pytest.mark.asyncio
async def test_engine_coalesces_submissions():
    metrics.reset()
    items1, exp1 = make_items(3)
    items2, exp2 = make_items(2, tamper_every=1)
    async with VerifyEngine(
        VerifyConfig(backend="cpu", max_wait=0.05, batch_size=64)
    ) as eng:
        f1 = asyncio.ensure_future(eng.verify(items1))
        f2 = asyncio.ensure_future(eng.verify(items2))
        got1, got2 = await asyncio.gather(f1, f2)
    assert got1 == exp1
    assert got2 == exp2
    # both submissions coalesced into one device batch
    assert metrics.get("verify.batches") == 1
    assert metrics.get("verify.items") == 5


@pytest.mark.asyncio
async def test_engine_empty():
    async with VerifyEngine(VerifyConfig(backend="oracle")) as eng:
        assert await eng.verify([]) == []


def test_engine_sync_path():
    items, expected = make_items(6, tamper_every=3)
    eng = VerifyEngine(VerifyConfig(backend="cpu"))
    assert eng.verify_sync(items) == expected


def _mixed_none_batch():
    """A batch mixing valid items with None-pubkey ('undecodable key',
    txverify auto-invalid) and infinity-pubkey items."""
    from tpunode.verify.ecdsa_cpu import Point

    items, expected = make_items(5, tamper_every=5)
    items.insert(1, (None, 123, 45, 67))
    expected.insert(1, False)
    items.insert(3, (Point(None, None), 123, 45, 67))
    expected.insert(3, False)
    return items, expected


def test_none_pubkey_verdicts_agree_across_backends():
    """VERDICT r2 weak#2: a None pubkey must yield valid=False per-item on
    every backend — not an exception that poisons the whole batch."""
    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.verify.kernel import verify_batch_tpu

    items, expected = _mixed_none_batch()
    assert verify_batch_cpu(items) == expected
    native = load_native_verifier()
    if native is not None:
        assert native.verify_batch(items) == expected
    assert verify_batch_tpu(items, pad_to=16) == expected


@pytest.mark.asyncio
async def test_engine_mixed_none_batch_per_item_verdicts():
    items, expected = _mixed_none_batch()
    for backend in ("cpu", "oracle"):
        async with VerifyEngine(
            VerifyConfig(backend=backend, max_wait=0.0)
        ) as eng:
            assert await eng.verify(items) == expected


@pytest.mark.asyncio
async def test_engine_survives_stalled_device_warmup(monkeypatch):
    """VERDICT r2 item 4: backend=auto on a box whose device backend hangs
    must still produce verdicts promptly via the CPU engine."""
    import threading

    hang = threading.Event()
    monkeypatch.setattr(
        VerifyEngine, "_warmup_fn", staticmethod(lambda bs, db=0: hang.wait(30) or "x")
    )
    cfg = VerifyConfig(backend="auto", max_wait=0.0, min_tpu_batch=1)
    async with VerifyEngine(cfg) as eng:
        assert eng.device_state == "warming"
        items, expected = make_items(4, tamper_every=2)
        got = await asyncio.wait_for(eng.verify(items), timeout=10)
        assert got == expected
    hang.set()


@pytest.mark.asyncio
async def test_engine_failed_warmup_falls_back(monkeypatch):
    def boom(bs, db=0):
        raise RuntimeError("no TPU device visible")

    monkeypatch.setattr(VerifyEngine, "_warmup_fn", staticmethod(boom))
    cfg = VerifyConfig(backend="auto", max_wait=0.0, min_tpu_batch=1)
    async with VerifyEngine(cfg) as eng:
        eng._warmup_done.wait(5)
        assert eng.device_state == "failed"
        items, expected = make_items(3)
        assert await eng.verify(items) == expected


@pytest.mark.asyncio
async def test_engine_forced_tpu_errors_when_unavailable(monkeypatch):
    def boom(bs, db=0):
        raise RuntimeError("no TPU device visible")

    monkeypatch.setattr(VerifyEngine, "_warmup_fn", staticmethod(boom))
    cfg = VerifyConfig(backend="tpu", max_wait=0.0, warmup_timeout=5)
    async with VerifyEngine(cfg) as eng:
        items, _ = make_items(2)
        with pytest.raises(RuntimeError, match="tpu backend unavailable"):
            await eng.verify(items)


def test_pack_items_roundtrip_and_degenerates():
    """RawBatch packing: valid items round-trip through to_tuples; the
    degenerate classes (None/infinity pubkey, out-of-range r/s incl. the
    oversized lax-DER case) pack to present=0 and verify False everywhere."""
    from tpunode.verify.ecdsa_cpu import Point, verify_batch_cpu
    from tpunode.verify.raw import pack_items

    items, expected = make_items(8, tamper_every=3)
    good = items[1]
    degenerates = [
        (None, good[1], good[2], good[3]),
        (Point(None, None), good[1], good[2], good[3]),
        (good[0], good[1], 0, good[3]),
        (good[0], good[1], good[2], CURVE_N),
        (good[0], good[1], 2**256 + 5, good[3]),  # oversized lax-DER r
    ]
    all_items = items + degenerates
    raw = pack_items(all_items)
    assert list(raw.present) == [1] * 8 + [0] * 5
    back = raw.to_tuples()
    for (q, z, r, s), (q2, z2, r2, s2) in zip(items, back[:8]):
        assert (q2.x, q2.y) == (q.x, q.y)
        assert (z2, r2, s2) == (z % CURVE_N, r, s)
    assert verify_batch_cpu(back) == expected + [False] * 5


@pytest.mark.asyncio
async def test_engine_raw_path_all_backends():
    """verify_raw == verify for the same logical items on every backend,
    including a mixed raw+tuple batch coalesced into one dispatch."""
    from tpunode.verify.raw import pack_items

    items, expected = make_items(32, tamper_every=5)
    raw = pack_items(items)
    for backend in ("cpu", "oracle"):
        async with VerifyEngine(
            VerifyConfig(backend=backend, max_wait=0.0)
        ) as eng:
            got_raw = await eng.verify_raw(raw)
            got_tup = await eng.verify(items)
            assert got_raw == got_tup == expected
    # mixed batch: raw and tuple submissions coalesce, per-payload results
    async with VerifyEngine(
        VerifyConfig(backend="cpu", max_wait=0.1, batch_size=128)
    ) as eng:
        t1 = asyncio.ensure_future(eng.verify_raw(pack_items(items[:10])))
        t2 = asyncio.ensure_future(eng.verify(items[10:20]))
        t3 = asyncio.ensure_future(eng.verify_raw(pack_items(items[20:])))
        assert await t1 == expected[:10]
        assert await t2 == expected[10:20]
        assert await t3 == expected[20:]


def test_engine_raw_sync_from_native_extract():
    """RawSigItems from the native extractor feed verify_raw_sync directly
    (duck-typed coercion), matching the tuple path."""
    pytest.importorskip("tpunode.txextract")
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import extract_raw, have_native_extract

    if not have_native_extract():
        pytest.skip("native extractor unavailable")
    txs = gen_signed_txs(20, inputs_per_tx=2, seed=77, invalid_every=4)
    data = b"".join(t.serialize() for t in txs)
    raw = extract_raw(data, len(txs))
    eng = VerifyEngine(VerifyConfig(backend="cpu", warmup=False))
    got = eng.verify_raw_sync(raw)
    assert got == eng.verify_sync(raw.to_verify_items())
    assert False in got and True in got


def test_run_tpu_collect_time_mosaic_error_propagates(monkeypatch):
    """JAX async dispatch surfaces Mosaic RUNTIME failures at collect
    time, not at the dispatch call.  On a chip that is present a Mosaic
    error means the kernel does not run: nothing re-dispatches the chunk
    through another program, the error leaves _run_tpu as it is."""
    import tpunode.verify.kernel as K
    from tpunode.verify.raw import pack_items

    items, _expected = make_items(6, tamper_every=2)
    raw = pack_items([it if len(it) > 4 else tuple(it) for it in items])
    calls = {"dispatch": 0, "collect": 0}

    def fake_dispatch(chunk, pad_to=None):
        calls["dispatch"] += 1
        return ("fake-array", len(chunk))

    def bad_collect(arr, count):
        calls["collect"] += 1
        raise RuntimeError("MosaicError: INTERNAL: Mosaic failed to compile")

    monkeypatch.setattr(K, "dispatch_batch_tpu_raw", fake_dispatch)
    monkeypatch.setattr(K, "collect_verdicts", bad_collect)
    eng = VerifyEngine(
        VerifyConfig(backend="cpu", warmup=False, min_tpu_batch=1)
    )
    with pytest.raises(RuntimeError, match="MosaicError"):
        eng._run_tpu([raw])
    assert calls == {"dispatch": 1, "collect": 1}  # no second program


def test_warmup_compiles_every_program_the_dispatcher_selects(monkeypatch):
    """The warmup builds both program variants (full, and the ECDSA-only
    schnorr_free one) at both shapes, cross-checks each and records its
    first-call seconds — so the first ECDSA-only lane of an IBD compiles
    nothing on the hot path.  A failure of any of them (here: a
    Mosaic-named one at the big shape) fails the warmup; the engine never
    stays "ready" at a smaller shape."""
    import types

    import jax as _jax

    import tpunode.verify.kernel as K
    from tpunode.events import events
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.verify.engine import _device_warmup

    seen = []

    def fake_vbt(items, pad_to=None):
        seen.append((pad_to, all(len(it) == 4 for it in items)))
        return verify_batch_cpu(items)

    monkeypatch.setattr(K, "verify_batch_tpu", fake_vbt)
    monkeypatch.setattr(
        _jax, "devices",
        lambda *a: [types.SimpleNamespace(platform="tpu",
                                          device_kind="fake")],
    )
    seq0 = events.seq()
    assert _device_warmup(16, 32) == "tpu:fake"
    # (shape, ECDSA-only?) — every program the dispatcher can select
    assert seen == [(16, False), (16, True), (32, False), (32, True)]
    rows = [e for e in events.tail(16, type="verify.compile")
            if e["seq"] > seq0]
    assert [(e["batch"], e["schnorr_free"]) for e in rows] == seen
    assert all(e["seconds"] >= 0 for e in rows)

    def big_shape_boom(items, pad_to=None):
        if pad_to == 32:
            raise RuntimeError("MosaicError: INTERNAL: scoped vmem exceeded")
        return verify_batch_cpu(items)

    monkeypatch.setattr(K, "verify_batch_tpu", big_shape_boom)
    with pytest.raises(RuntimeError, match="MosaicError"):
        _device_warmup(16, 32)


@pytest.mark.asyncio
async def test_forced_tpu_does_not_ladder_down(monkeypatch):
    """backend="tpu" means tpu: a batch that fails on the device is not
    served by the cpu/oracle rungs with only a counter to show it — the
    error reaches the waiters.  backend="auto" keeps its ladder."""
    from tpunode.metrics import metrics

    def boom(self, payloads, host=None):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(VerifyEngine, "_warmup_fn",
                        staticmethod(lambda bs, db=0: "tpu:fake"))
    monkeypatch.setattr(VerifyEngine, "_run_tpu", boom)
    items, expected = make_items(4, tamper_every=2)

    before = {k: metrics.get(k) for k in (
        "verify.failovers", "verify.cpu_items", "verify.oracle_items")}
    async with VerifyEngine(
        VerifyConfig(backend="tpu", max_wait=0.0, warmup_timeout=5)
    ) as eng:
        with pytest.raises(RuntimeError, match="device fell over"):
            await eng.verify(items)
    assert {k: metrics.get(k) for k in before} == before

    async with VerifyEngine(
        VerifyConfig(backend="auto", max_wait=0.0, min_tpu_batch=1)
    ) as eng:
        eng._warmup_done.wait(5)
        assert eng.device_state == "ready"
        assert await eng.verify(items) == expected  # laddered down
    assert metrics.get("verify.failovers") == before["verify.failovers"] + 1


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """Unset, the cache lives at the fixed <checkout>/.jax_cache; where
    JAX_COMPILATION_CACHE_DIR is set it wins and no directory is set in
    code (a fresh process shows jax itself picked it up)."""
    import subprocess
    import sys

    import jax

    from tpunode.verify import engine as E

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append((k, v)), real_update(k, v))[1],
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert E.enable_compile_cache() == E._DEFAULT_CACHE
    assert ("jax_compilation_cache_dir", E._DEFAULT_CACHE) in updates
    assert E._DEFAULT_CACHE == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )

    updates.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    E.enable_compile_cache()
    assert not [k for k, _ in updates if k == "jax_compilation_cache_dir"]

    out = subprocess.run(
        [sys.executable, "-c",
         "from tpunode.verify.engine import enable_compile_cache as e; "
         "print(e())"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)


@pytest.mark.asyncio
async def test_all_rungs_failure_fails_only_that_batch(monkeypatch):
    """ISSUE 7 satellite: the waiter-failure path (a batch that fails on
    EVERY ladder rung) fails only that batch's waiters, and the dispatch
    loop survives to serve the next batch."""
    eng = VerifyEngine(VerifyConfig(backend="oracle", max_wait=0.0))
    calls = {"n": 0}
    orig = eng._dispatch_multi

    def flaky(payloads, target=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("all rungs down")
        return orig(payloads, target)

    monkeypatch.setattr(eng, "_dispatch_multi", flaky)
    items, expected = make_items(4, tamper_every=2)
    async with eng:
        with pytest.raises(RuntimeError, match="all rungs down"):
            await eng.verify(items)
        # the queue loop survived: the next batch verifies normally
        assert await asyncio.wait_for(eng.verify(items), 10) == expected
    assert calls["n"] == 2


@pytest.mark.asyncio
async def test_concurrent_waiters_all_fail_then_recover(monkeypatch):
    """Coalesced-batch flavor of the waiter-failure pin: every waiter of
    the failed batch gets the exception (none left pending), then the
    engine keeps serving."""
    eng = VerifyEngine(
        VerifyConfig(backend="oracle", max_wait=0.05, batch_size=64)
    )
    calls = {"n": 0}
    orig = eng._dispatch_multi

    def flaky(payloads, target=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return orig(payloads, target)

    monkeypatch.setattr(eng, "_dispatch_multi", flaky)
    items1, _ = make_items(3)
    items2, exp2 = make_items(2, tamper_every=1)
    async with eng:
        f1 = asyncio.ensure_future(eng.verify(items1))
        f2 = asyncio.ensure_future(eng.verify(items2))
        r1, r2 = await asyncio.gather(f1, f2, return_exceptions=True)
        assert isinstance(r1, RuntimeError) and isinstance(r2, RuntimeError)
        assert await eng.verify(items2) == exp2


@pytest.mark.asyncio
async def test_rung_failure_fails_over_within_dispatch(monkeypatch):
    """ISSUE 7 ladder: a cpu-rung crash re-dispatches the same batch on
    the python oracle — waiters see verdicts, not the exception."""
    eng = VerifyEngine(VerifyConfig(backend="cpu", max_wait=0.0))
    seen = []
    orig = eng._run_backend

    def flaky(rung, payloads, total):
        seen.append(rung)
        if rung == "cpu":
            raise RuntimeError("native engine crashed")
        return orig(rung, payloads, total)

    monkeypatch.setattr(eng, "_run_backend", flaky)
    items, expected = make_items(6, tamper_every=3)
    async with eng:
        assert await eng.verify(items) == expected
    assert seen[-1] == "oracle"


# --- the lane rule by class, through the engine (ISSUE 37) -------------------


def _spy_lanes(eng) -> list:
    """Item counts of the payloads of every lane the engine dispatches."""
    lanes: list = []
    orig = eng._dispatch_multi

    def spy(payloads, target=None):
        lanes.append([len(p) for p in payloads])
        return orig(payloads, target)

    eng._dispatch_multi = spy
    return lanes


def _cut_counts() -> dict:
    return {
        k: v for k, v in metrics.snapshot().items()
        if k.startswith("sched.lanes_cut_")
    }


@pytest.mark.asyncio
async def test_block_cuts_at_its_own_deadline_and_takes_ibd_along():
    """A lone `ibd` submission outlives 1 x max_wait (nobody waits on
    it); a `block` one queued behind it cuts the lane at ITS deadline,
    before the `ibd` one's own, and the `ibd` items ride in the room
    left."""
    metrics.reset()
    ibd_items, ibd_exp = make_items(3, tamper_every=2)
    blk_items, blk_exp = make_items(2)
    async with VerifyEngine(
        VerifyConfig(backend="cpu", batch_size=64, max_wait=0.2)
    ) as eng:
        lanes = _spy_lanes(eng)
        t0 = time.monotonic()
        f1 = asyncio.ensure_future(eng.verify(ibd_items, priority="ibd"))
        await asyncio.sleep(0.1)
        f2 = asyncio.ensure_future(eng.verify(blk_items, priority="block"))
        assert await f2 == blk_exp and await f1 == ibd_exp
        # not at the `ibd` one's 1 x (0.2 s), as one deadline for every
        # class would have it, but at the `block` one's own
        assert time.monotonic() - t0 >= 0.3
    assert lanes == [[2, 3]]
    assert _cut_counts() == {'sched.lanes_cut_deadline{priority="block"}': 1}


@pytest.mark.parametrize("cls,n,want_lanes,want_slots,want_cuts", [
    # ibd between the shapes: full small lanes, the rest on its deadline
    ("ibd", 20, [[8], [8], [4]], 24, {"full": 2, "deadline": 1}),
    # ibd over the big shape: as before, then the small rule again
    ("ibd", 40, [[32], [8]], 40, {"full": 2}),
    # a class with a waiter between the shapes: one lane, padded to big
    ("block", 20, [[20]], 32, {"deadline": 1}),
    ("bulk", 33, [[32], [1]], 40, {"full": 1, "deadline": 1}),
])
@pytest.mark.asyncio
async def test_lanes_slots_and_cut_counters_by_class(
    monkeypatch, cls, n, want_lanes, want_slots, want_cuts
):
    """The tpu rung behind a fake device: what is cut, what it is padded
    to (`verify.tpu_slots` beside `verify.tpu_items`) and why."""
    from tests.test_chaos import _fake_device

    _fake_device(monkeypatch)
    metrics.reset()
    items, expected = make_items(n, tamper_every=5)
    cfg = VerifyConfig(
        backend="auto", batch_size=8, device_batch=32, min_tpu_batch=1,
        max_wait=0.02, pipeline_depth=1,
    )
    async with VerifyEngine(cfg) as eng:
        eng._warmup_done.wait(5)
        assert eng.device_state == "ready"
        lanes = _spy_lanes(eng)
        assert await eng.verify(items, priority=cls) == expected
    assert lanes == want_lanes
    assert metrics.get("verify.tpu_items") == n
    assert metrics.get("verify.tpu_slots") == want_slots
    assert _cut_counts() == {
        'sched.lanes_cut_%s{priority="%s"}' % (r, cls): c
        for r, c in want_cuts.items()
    }
    assert metrics.get("sched.lanes") == len(want_lanes)
