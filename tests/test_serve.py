"""servesrv acceptance pins (ISSUE 20): the multi-tenant verification
service over real local sockets — firehose dedup (exactly one verify per
unique item, per-tenant counters exact), quota isolation (one tenant's
flood cannot starve another), inflight-cap throttling, QoS shedding
under SLO burn, auth refusal, and the receipt binding of every
dispatched batch."""

from __future__ import annotations

import asyncio
import hashlib
import random

import pytest

from tpunode.receipts import ReceiptLog, _jsonable_modes, audit
from tpunode.serve import (
    MAX_TENANTS,
    ServeServer,
    TenantConfig,
    _kernel_modes_now,
    tenant_names,
)


class StubEngine:
    """Counting verify engine: records every item it is asked to verify
    (the firehose pin is that this list holds each unique row exactly
    once), optionally parks inside verify() on a gate event."""

    def __init__(self, gate: asyncio.Event | None = None, verdict=True):
        self.batches: list[list] = []
        self.tenants: list = []
        self.gate = gate
        self.verdict = verdict
        self.last_rung = "cpu"

    @property
    def item_count(self) -> int:
        return sum(len(b) for b in self.batches)

    async def verify(self, items, priority="bulk", tenant=None):
        self.batches.append(list(items))
        self.tenants.append((tenant, priority))
        if self.gate is not None:
            await self.gate.wait()
        await asyncio.sleep(0)  # real suspension: coalescing is exercised
        return [self.verdict] * len(items)


def _rows(n: int) -> list:
    """n distinct wire rows.  Cache identity is the row *strings* (the
    server hashes them before parsing), so these need not decode."""
    return [["%064x" % i, "02" + "ab" * 32, "cd" * 64] for i in range(n)]


def _key(row) -> bytes:
    return hashlib.sha256("|".join(str(c) for c in row).encode()).digest()


async def _rpc(port: int, frame: dict) -> dict:
    r, w = await asyncio.open_connection("127.0.0.1", port)
    try:
        return await _send(r, w, frame)
    finally:
        w.close()


async def _send(r, w, frame: dict) -> dict:
    import json

    data = json.dumps(frame).encode()
    w.write(len(data).to_bytes(4, "big") + data)
    await w.drain()
    size = int.from_bytes(await r.readexactly(4), "big")
    return json.loads(await r.readexactly(size))


def _frame(tenant: str, rows, fid=0) -> dict:
    return {"tenant": tenant, "token": f"tok-{tenant}", "items": rows,
            "id": fid}


def _tenants(*specs) -> list:
    return [
        TenantConfig(name=n, token=f"tok-{n}", priority=p, **kw)
        for n, p, kw in specs
    ]


@pytest.mark.asyncio
async def test_firehose_dedup_exactly_one_verify_per_unique_item(
    threadsan_armed,
):
    """ISSUE 20 acceptance: four tenants of four classes fire
    duplicate-heavy frames concurrently over real sockets; the shared
    verdict cache (+ in-flight coalescing) means the engine verifies
    each unique row EXACTLY once, and the per-tenant frame/item/hit
    counters account for every submitted item."""
    eng = StubEngine()
    pool = _rows(32)
    tenants = _tenants(
        ("alpha", "block", {}), ("beta", "mempool", {}),
        ("gamma", "ibd", {}), ("delta", "bulk", {}),
    )
    frames_per, items_per = 8, 12
    async with ServeServer(eng, tenants, port=0) as srv:
        async def one_tenant(ti: int, name: str):
            rng = random.Random(ti)
            r, w = await asyncio.open_connection("127.0.0.1", srv.port)
            got = []
            try:
                for f in range(frames_per):
                    # guarantee full pool coverage across the fleet,
                    # then Zipf-ish duplicates on top
                    idxs = [(ti * frames_per + f) * items_per + j
                            for j in range(items_per)]
                    rows = [pool[i % 32] if i % 3 else pool[rng.randrange(8)]
                            for i in idxs]
                    got.append(await _send(r, w, _frame(name, rows, f)))
            finally:
                w.close()
            return got

        replies = await asyncio.gather(
            *(one_tenant(i, t.name) for i, t in enumerate(tenants))
        )
        stats = srv.stats()

    # every frame answered with real verdicts, none shed/throttled
    flat = [rep for per in replies for rep in per]
    assert len(flat) == 4 * frames_per
    assert all(rep["ok"] and len(rep["verdicts"]) == items_per
               and all(v is True for v in rep["verdicts"]) for rep in flat)
    # the firehose pin: 384 submitted items, 32 unique, EXACTLY 32 verified
    assert eng.item_count == 32
    assert len({str(i) for b in eng.batches for i in b}) == 32
    # per-tenant accounting is exact and conserves items
    tstats = stats["tenants"]
    assert set(tstats) == {"alpha", "beta", "gamma", "delta"}
    for name in tstats:
        ts = tstats[name]
        assert ts["frames"] == frames_per
        assert ts["items"] == frames_per * items_per
        assert ts["cache_hits"] + ts["verified"] == ts["items"]
        assert ts["shed"] == 0 and ts["throttled"] == 0
        assert ts["inflight"] == 0
    assert sum(ts["verified"] for ts in tstats.values()) == 32
    # cached counts in the replies agree with the counters
    assert sum(rep["cached"] for rep in flat) == sum(
        ts["cache_hits"] for ts in tstats.values()
    )
    # engine saw the submitting tenant's identity and lane
    assert all(t in {"alpha", "beta", "gamma", "delta"}
               for t, _ in eng.tenants)
    assert stats["cache"]["entries"] == 32


@pytest.mark.asyncio
async def test_quota_isolation_flood_is_throttled_not_neighbors(
    threadsan_armed,
):
    """One tenant blowing through its token bucket gets explicit
    ``throttled`` replies with a ``retry_after`` — and costs zero verify
    work — while a well-behaved tenant on the same server is served
    normally the whole time."""
    eng = StubEngine()
    tenants = _tenants(
        ("flood", "bulk", {"rate": 1.0, "burst": 10.0}),
        ("calm", "mempool", {}),
    )
    pool = _rows(64)
    async with ServeServer(eng, tenants, port=0) as srv:
        # burst allows the first 10 items; the 12-item frame after that
        # must be refused (bucket refills 1/s — nowhere near 12)
        first = await _rpc(srv.port, _frame("flood", pool[:10]))
        assert first["ok"] is True and len(first["verdicts"]) == 10
        flood = [await _rpc(srv.port, _frame("flood", pool[10:22], i))
                 for i in range(3)]
        calm = [await _rpc(srv.port, _frame("calm", pool[32 + 8 * i:40 + 8 * i], i))
                for i in range(3)]
        stats = srv.stats()
    for rep in flood:
        assert rep["ok"] is False and rep["error"] == "throttled"
        assert rep["reason"] == "rate"
        assert rep["retry_after"] > 0
    for rep in calm:
        assert rep["ok"] is True and len(rep["verdicts"]) == 8
    # refusals spent nothing: only admitted items reached the engine
    assert eng.item_count == 10 + 24
    ts = stats["tenants"]
    assert ts["flood"]["throttled"] == 36
    assert ts["calm"]["throttled"] == 0 and ts["calm"]["verified"] == 24


@pytest.mark.asyncio
async def test_inflight_cap_throttles_while_engine_is_busy():
    """The second quota stage: a tenant with ``max_inflight`` items
    already parked in the engine gets reason="inflight" — and is served
    again once the engine drains."""
    gate = asyncio.Event()
    eng = StubEngine(gate=gate)
    tenants = _tenants(("t", "bulk", {"max_inflight": 4}))
    pool = _rows(8)
    async with ServeServer(eng, tenants, port=0) as srv:
        parked = asyncio.create_task(_rpc(srv.port, _frame("t", pool[:4])))
        while not eng.batches:  # engine now holds 4 items for "t"
            await asyncio.sleep(0.001)
        refused = await _rpc(srv.port, _frame("t", pool[4:6]))
        assert refused["ok"] is False and refused["error"] == "throttled"
        assert refused["reason"] == "inflight"
        gate.set()
        first = await parked
        assert first["ok"] is True and len(first["verdicts"]) == 4
        again = await _rpc(srv.port, _frame("t", pool[4:6]))
        assert again["ok"] is True and len(again["verdicts"]) == 2


@pytest.mark.asyncio
async def test_shed_under_burn_lowest_class_only_and_recovers(
    threadsan_armed,
):
    """QoS shedding: while the fast SLO window burns, ONLY the lowest
    registered class is refused — with error verdicts, never silence —
    block-class traffic is untouched, and the shed class serves again
    the moment the burn clears."""
    eng = StubEngine()
    burning: list = []
    tenants = _tenants(("miner", "block", {}), ("batch", "bulk", {}),
                       ("feed", "mempool", {}))
    pool = _rows(48)
    async with ServeServer(
        eng, tenants, port=0, slo_burning=lambda: list(burning)
    ) as srv:
        burning.append("verdict-latency-block")
        shed = await _rpc(srv.port, _frame("batch", pool[:6]))
        served_block = await _rpc(srv.port, _frame("miner", pool[6:12]))
        served_mid = await _rpc(srv.port, _frame("feed", pool[12:18]))
        burning.clear()
        recovered = await _rpc(srv.port, _frame("batch", pool[18:24]))
        stats = srv.stats()
    assert shed["ok"] is False and shed["error"] == "shed"
    assert shed["reason"] == "slo-burn"
    assert shed["verdicts"] == [None] * 6  # explicit, one per item
    assert served_block["ok"] is True and served_mid["ok"] is True
    assert recovered["ok"] is True and len(recovered["verdicts"]) == 6
    ts = stats["tenants"]
    assert ts["batch"]["shed"] == 6 and ts["miner"]["shed"] == 0
    assert ts["feed"]["shed"] == 0  # only the LOWEST class sheds


@pytest.mark.asyncio
async def test_auth_refusal_and_wire_contract():
    eng = StubEngine()
    async with ServeServer(eng, _tenants(("t", "bulk", {})), port=0) as srv:
        bad_token = await _rpc(srv.port, {
            "tenant": "t", "token": "wrong", "items": _rows(1),
        })
        unknown = await _rpc(srv.port, _frame("ghost", _rows(1)))
        both = await _rpc(srv.port, {
            "tenant": "t", "token": "tok-t", "items": _rows(1), "raw": [],
        })
        empty = await _rpc(srv.port, _frame("t", []))
        stats = srv.stats()
    assert bad_token == {"ok": False, "error": "auth", "id": None}
    assert unknown["error"] == "auth"
    assert "exactly one of" in both["error"]
    assert empty["ok"] is True and empty["verdicts"] == []
    assert eng.item_count == 0  # none of the above reached the engine
    # auth failures never count as tenant traffic
    assert stats["tenants"]["t"]["frames"] == 2  # the both= and empty frames


@pytest.mark.asyncio
async def test_receipts_bind_batch_verdicts_modes_and_rung(tmp_path):
    """Every dispatched batch leaves a chained receipt binding (batch
    digest, verdict digest, kernel-mode tuple, serving rung) — the
    digests are recomputable from the wire rows alone, and the log
    audits clean."""
    eng = StubEngine()
    d = str(tmp_path / "receipts")
    receipts = ReceiptLog(d)
    rows = _rows(3)
    async with ServeServer(
        eng, _tenants(("t", "bulk", {})), port=0, receipts=receipts
    ) as srv:
        rep = await _rpc(srv.port, _frame("t", rows))
        dup = await _rpc(srv.port, _frame("t", rows))  # pure cache hits
    assert rep["ok"] is True and dup["cached"] == 3
    assert receipts.seq == 1  # cache-hit frames dispatch no batch
    (rec,) = receipts.records(0, 10)
    assert rec["batch"] == hashlib.sha256(
        b"".join(_key(r) for r in rows)
    ).hexdigest()
    assert rec["verdict"] == hashlib.sha256(bytes([1, 1, 1])).hexdigest()
    assert rec["modes"] == _jsonable_modes(_kernel_modes_now())
    assert rec["rung"] == "cpu"  # the stub engine's last_rung
    receipts.close()
    res = audit(d)
    assert res["ok"] is True and res["records"] == 1


@pytest.mark.asyncio
async def test_receipt_modes_are_the_pinned_literal(tmp_path):
    """PR 29 left ONE kernel formulation; a receipt keeps binding it with
    the bytes receipts carried while it was selectable (the defaults'
    tuple), so logs written before and after audit alike."""
    import json

    import tpunode.verify.kernel  # noqa: F401 — the device kernel is in play

    pinned = ["shift_add", "half", "lazy", "projective", "tree", "scan", 4]
    d = str(tmp_path / "receipts")
    receipts = ReceiptLog(d)
    async with ServeServer(
        StubEngine(), _tenants(("t", "bulk", {})), port=0, receipts=receipts
    ) as srv:
        rep = await _rpc(srv.port, _frame("t", _rows(2)))
    assert rep["ok"] is True
    (rec,) = receipts.records(0, 10)
    assert rec["modes"] == pinned
    assert json.dumps(rec["modes"]) == (
        '["shift_add", "half", "lazy", "projective", "tree", "scan", 4]'
    )
    receipts.close()
    assert audit(d)["ok"] is True


def test_tenant_registry_is_bounded():
    """The ``tenant=`` label source contract: names validated, unique,
    and hard-capped at MAX_TENANTS."""
    assert tenant_names(["a", "b-2", "C_3"]) == ["a", "b-2", "C_3"]
    with pytest.raises(ValueError, match="invalid tenant name"):
        tenant_names(["bad name"])
    with pytest.raises(ValueError, match="invalid tenant name"):
        tenant_names(["x" * 33])
    with pytest.raises(ValueError, match="duplicate"):
        tenant_names(["a", "a"])
    with pytest.raises(ValueError, match="MAX_TENANTS"):
        tenant_names([f"t{i}" for i in range(MAX_TENANTS + 1)])
    with pytest.raises(ValueError, match="priority"):
        TenantConfig(name="t", token="k", priority="vip")
