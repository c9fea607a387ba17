"""Multi-chip shard_map verify on the virtual 8-device CPU mesh (conftest
sets --xla_force_host_platform_device_count=8)."""

import random

import pytest

pytestmark = pytest.mark.heavy  # compile-heavy tier (pytest.ini)

jax = pytest.importorskip("jax")

from tpunode.verify.ecdsa_cpu import CURVE_N, GENERATOR, point_mul, sign, verify
from tpunode.verify.multichip import make_mesh, verify_batch_sharded

rng = random.Random(20260729)


def make_items(n, tamper_every=5):
    items = []
    expect = []
    for i in range(n):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
        if i % tamper_every == 1:
            z ^= 1  # corrupt the message
        items.append((pub, z, r, s))
        expect.append(verify(pub, z, r, s))
    return items, expect


def test_mesh_uses_all_devices():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices()) == 8


def test_hybrid_mesh_topology():
    """ISSUE 13: make_hybrid_mesh carves the (host, chip) grid — virtual
    2x4 over the 8 CPU devices — with the per-host axis holding
    contiguous local devices; host_submesh slices one row back out as a
    1-D mesh; over-subscription fails loudly (a silently-shrunk pod must
    not masquerade as the requested topology)."""
    from tpunode.verify.multichip import (
        HYBRID_AXES,
        host_submesh,
        make_hybrid_mesh,
    )

    mesh = make_hybrid_mesh(2, 4)
    assert mesh.devices.shape == (2, 4)
    assert tuple(mesh.axis_names) == HYBRID_AXES == ("host", "chip")
    row1 = host_submesh(mesh, 1)
    assert row1.devices.shape == (4,) and tuple(row1.axis_names) == ("batch",)
    assert [d.id for d in row1.devices.flat] == [
        d.id for d in mesh.devices[1]
    ]
    # defaults: one virtual host per device in a single process
    assert make_hybrid_mesh().devices.shape == (8, 1)
    # partial specs derive the other axis
    assert make_hybrid_mesh(hosts=4).devices.shape == (4, 2)
    assert make_hybrid_mesh(chips_per_host=2).devices.shape == (4, 2)
    # a 1-D mesh is its own (only) row
    lm = make_mesh(4)
    assert host_submesh(lm, 0) is lm
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_hybrid_mesh(4, 4)


@pytest.mark.slow  # two fresh XLA shard_map compiles (~2-3 min on this
# box): the tier-1 870s budget is seed-saturated, so the hybrid parity
# evidence lives in the slow tier (ran green this session) — the cheap
# topology/cache pins above stay tier-1
def test_hybrid_sharded_matches_oracle():
    """Hybrid-mesh parity (CPU dryrun, the 2x4 virtual topology): the
    batch axis shards over host AND chip jointly, verdicts are
    bit-identical to the oracle, and the psum over both axes agrees."""
    from tpunode.verify.multichip import make_hybrid_mesh

    mesh = make_hybrid_mesh(2, 4)
    items, expect = make_items(24)
    got = verify_batch_sharded(items, mesh=mesh)
    assert got == expect
    assert any(got) and not all(got)
    # ragged batch: mesh-quantum padding still rejects pad lanes for free
    items2, expect2 = make_items(11)
    assert verify_batch_sharded(items2, mesh=mesh) == expect2


def test_hybrid_fn_cache_keys_on_mesh_topology():
    """sharded_verify_fn caches per mesh topology: the 2x4 hybrid, the
    8x1 hybrid and the 1-D local mesh are distinct compiled entries;
    the same mesh hits its cache (no jit wrapper churn)."""
    from tpunode.verify.multichip import make_hybrid_mesh, sharded_verify_fn

    h24 = make_hybrid_mesh(2, 4)
    h81 = make_hybrid_mesh(8, 1)
    local = make_mesh()
    f1 = sharded_verify_fn(h24, kernel="xla")
    f2 = sharded_verify_fn(h81, kernel="xla")
    f3 = sharded_verify_fn(local, kernel="xla")
    assert len({id(f1), id(f2), id(f3)}) == 3
    assert sharded_verify_fn(make_hybrid_mesh(2, 4), kernel="xla") is f1


def test_sharded_matches_oracle():
    items, expect = make_items(24)
    got = verify_batch_sharded(items)
    assert got == expect
    assert any(got) and not all(got)


def test_sharded_pads_to_mesh_multiple():
    # 10 items on 8 devices: padding lanes must not leak into results
    items, expect = make_items(10)
    got = verify_batch_sharded(items)
    assert got == expect


def test_sharded_submesh():
    mesh = make_mesh(4)
    assert mesh.devices.size == 4
    items, expect = make_items(8)
    assert verify_batch_sharded(items, mesh=mesh) == expect


@pytest.mark.slow  # a full XLA shard_map compile (~90s on this box): the
# tier-1 870s budget is seed-saturated, so the mesh-rung parity evidence
# lives in the slow tier (ran green this session; the cheap gating pins
# are in test_sched.py)
def test_dispatch_raw_sharded_matches_oracle():
    """ISSUE 10: the engine's mesh rung — async raw-batch dispatch over
    a mesh (dispatch_raw_sharded + collect_verdicts) is bit-identical to
    the oracle, including the mesh-quantum padding of a ragged batch."""
    from tpunode.verify.kernel import collect_verdicts
    from tpunode.verify.multichip import dispatch_raw_sharded
    from tpunode.verify.raw import pack_items

    items, expect = make_items(22)  # NOT a multiple of the 8-wide mesh
    raw = pack_items(items)
    mesh = make_mesh()
    got = collect_verdicts(*dispatch_raw_sharded(raw, mesh))
    assert got == expect
    # pad_to below the batch is ignored; above it aligns up
    got2 = collect_verdicts(*dispatch_raw_sharded(raw, mesh, pad_to=64))
    assert got2 == expect


@pytest.mark.slow  # full shard_map compile on the hybrid mesh (~90s):
# same budget discipline as the raw-sharded pin above
def test_dispatch_raw_sharded_hybrid_mesh():
    """ISSUE 13: the raw-dispatch path over a HYBRID (2x4) mesh — the
    fleet's whole-mesh rung — is bit-identical to the oracle, ragged
    batches included."""
    from tpunode.verify.kernel import collect_verdicts
    from tpunode.verify.multichip import dispatch_raw_sharded, make_hybrid_mesh
    from tpunode.verify.raw import pack_items

    items, expect = make_items(21)  # NOT a multiple of the 8-device grid
    raw = pack_items(items)
    mesh = make_hybrid_mesh(2, 4)
    got = collect_verdicts(*dispatch_raw_sharded(raw, mesh))
    assert got == expect


@pytest.mark.slow  # per-host sub-mesh compiles (~2 XLA shard_map
# programs): the cheap fleet pins live in test_sched with the simulated
# device; this is the REAL-compile parity evidence for the fleet rung
def test_engine_fleet_serves_lanes_over_host_submeshes():
    """ISSUE 13 engine wiring: with mesh_hosts=2 the device rung carves
    the 2x4 hybrid rows and each host worker dispatches its lanes over
    its own 4-device sub-mesh — verdicts match the per-item
    expectations (device path simulated as in test_engine's affine pin:
    state forced ready, cpu-jax IS the device)."""
    import asyncio

    from tpunode.verify.engine import VerifyConfig, VerifyEngine

    items, expect = make_items(20)

    async def run() -> list:
        cfg = VerifyConfig(
            backend="auto", batch_size=8, device_batch=8, min_tpu_batch=1,
            max_wait=0.02, warmup=False, mesh_hosts=2, pipeline_depth=1,
        )
        eng = VerifyEngine(cfg)
        eng._device_state = "ready"  # cpu-jax is the device
        async with eng:
            f1 = asyncio.ensure_future(eng.verify(items[:11]))
            f2 = asyncio.ensure_future(eng.verify(items[11:]))
            g1, g2 = await asyncio.gather(f1, f2)
        assert eng._fleet_hybrid is not None
        # a host that never dispatched has no sub-mesh yet
        assert any(hs.mesh is not None for hs in eng._hosts.values())
        return g1 + g2

    assert asyncio.run(run()) == expect


@pytest.mark.slow  # same budget discipline as the raw-sharded pin above
def test_engine_mesh_rung_serves_packed_lanes():
    """ISSUE 10 engine wiring: with mesh_devices set, the tpu rung
    shards packed lanes over the CPU-mesh dryrun and verdicts match the
    per-item expectations (device path simulated as in test_engine's
    affine pin: state forced ready, cpu-jax IS the device)."""
    import asyncio

    from tpunode.verify.engine import VerifyConfig, VerifyEngine

    items, expect = make_items(20)

    async def run() -> list:
        cfg = VerifyConfig(
            backend="auto", batch_size=8, device_batch=8, min_tpu_batch=1,
            max_wait=0.02, warmup=False, mesh_devices=4, pipeline_depth=2,
        )
        eng = VerifyEngine(cfg)
        eng._device_state = "ready"  # cpu-jax is the device
        async with eng:
            f1 = asyncio.ensure_future(eng.verify(items[:11]))
            f2 = asyncio.ensure_future(eng.verify(items[11:]))
            g1, g2 = await asyncio.gather(f1, f2)
        assert eng._mesh_obj is not None
        return g1 + g2

    assert asyncio.run(run()) == expect


def test_pallas_kernel_inside_shard_map_interpret():
    """Pin the Pallas-inside-shard_map path (VERDICT r3 item 7): the Mosaic
    kernel in interpret mode, small block, on a 2-shard CPU mesh — so the
    in_specs / per-shard BLOCK alignment logic of multichip.py is exercised
    without TPU hardware."""
    import numpy as np

    from tpunode.verify.kernel import prepare_batch
    from tpunode.verify.multichip import _put_lane, sharded_verify_fn

    mesh = make_mesh(2)
    block = 8
    items, expect = make_items(2 * block)  # one block per shard
    prep = prepare_batch(items, pad_to=2 * block)
    fn = sharded_verify_fn(mesh, kernel="pallas", interpret=True, block=block)
    buf = _put_lane(prep, mesh)
    assert len(buf.addressable_shards) == 2  # the batch axis is split
    assert buf.addressable_shards[0].data.shape == (prep.buf.shape[0], block)
    ok, total = fn(buf)
    got = [bool(b) for b in np.asarray(ok)]
    assert got == expect
    assert int(total) == sum(expect)
    # padding path: 3 items over 2 shards pads each shard to one block
    items3, expect3 = make_items(3)
    prep3 = prepare_batch(items3, pad_to=2 * block)
    ok3, total3 = fn(_put_lane(prep3, mesh))
    assert [bool(b) for b in np.asarray(ok3)[:3]] == expect3
    assert int(total3) == sum(expect3)  # padded lanes reject for free


def test_sharded_mixed_algorithms():
    """All three signature algorithms through shard_map on the CPU mesh:
    the per-lane schnorr/bip340 flags are a row of the lane's one buffer
    and shard with the batch like every other row."""
    from tpunode.verify.ecdsa_cpu import (
        bip340_challenge,
        lift_x,
        schnorr_challenge,
        sign_bip340,
        sign_schnorr,
        verify_batch_cpu,
    )

    items = []
    for i in range(16):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        m = rng.getrandbits(256)
        if i % 3 == 0:
            r, s = sign(priv, m, rng.getrandbits(256) % CURVE_N or 1)
            if i % 6 == 3:
                s = (s + 1) % CURVE_N or 1
            items.append((pub, m, r, s))
        elif i % 3 == 1:
            r, s = sign_schnorr(priv, m, rng.getrandbits(256))
            e = schnorr_challenge(r, pub, m)
            if i % 6 == 4:
                e = (e + 1) % CURVE_N
            items.append((pub, e, r, s, "schnorr"))
        else:
            r, s = sign_bip340(priv, m, rng.getrandbits(256))
            e = bip340_challenge(r, pub.x, m)
            if i % 6 == 5:
                e = (e + 1) % CURVE_N
            items.append((lift_x(pub.x), e, r, s, "bip340"))
    expect = verify_batch_cpu(items)
    mesh = make_mesh(4)
    got = verify_batch_sharded(items, mesh=mesh)
    assert got == expect
    assert True in expect and False in expect


def test_sharded_propagates_mosaic_error(monkeypatch):
    """Inside shard_map too: a pallas trace/compile failure on an all-TPU
    mesh propagates — the sharded path never re-runs the batch through
    the XLA program on the same mesh."""
    import tpunode.verify.multichip as MC
    import tpunode.verify.pallas_kernel as PK

    def mosaic_boom(*a, **k):
        raise RuntimeError("MosaicError: INTERNAL: Mosaic failed to compile")

    monkeypatch.setattr(MC, "_mesh_is_tpu", lambda mesh: True)
    monkeypatch.setattr(PK, "verify_blocked_impl", mosaic_boom)
    MC._FN_CACHE.clear()
    try:
        items, _ = make_items(16)
        with pytest.raises(RuntimeError, match="MosaicError"):
            MC.verify_batch_sharded(items, mesh=MC.make_mesh())
    finally:
        MC._FN_CACHE.clear()


def test_sharded_schnorr_free_verdict_parity():
    """ADVICE r5 #3: prep.schnorr_free threads through sharded_verify_fn
    so ECDSA-only sharded batches run the pallas variant with the
    acceptance pows pruned.  Verdicts must be bit-identical both ways,
    and the two variants must be cached as distinct executables."""
    import numpy as np

    from tpunode.verify.kernel import prepare_batch
    from tpunode.verify.multichip import _put_lane, sharded_verify_fn

    mesh = make_mesh(2)
    block = 8
    items, expect = make_items(2 * block)  # ECDSA-only
    prep = prepare_batch(items, pad_to=2 * block)
    assert prep.schnorr_free  # the one safe derivation (host flags)
    buf = _put_lane(prep, mesh)
    fn_full = sharded_verify_fn(mesh, kernel="pallas", interpret=True,
                                block=block)
    fn_free = sharded_verify_fn(mesh, kernel="pallas", interpret=True,
                                block=block, schnorr_free=True)
    assert fn_full is not fn_free  # distinct cache entries
    ok_full, tot_full = fn_full(buf)
    ok_free, tot_free = fn_free(buf)
    got_full = [bool(b) for b in np.asarray(ok_full)]
    got_free = [bool(b) for b in np.asarray(ok_free)]
    assert got_full == expect
    assert got_free == expect
    assert int(tot_full) == int(tot_free) == sum(expect)
    # the XLA path ignores the static flag (runtime lax.cond gating):
    # same cache entry either way
    fx1 = sharded_verify_fn(mesh, kernel="xla")
    fx2 = sharded_verify_fn(mesh, kernel="xla", schnorr_free=True)
    assert fx1 is fx2
