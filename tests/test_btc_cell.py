"""The BTC deployment's benchmark files against the program (ISSUE 42):
``chipbench/gen_btc.py`` -> the program's extractor (native and Python) +
CPU verify -> equal to ``chipbench/reference_btc.py`` and to construction,
per input kind and per adversarial kind; BIP340 / BIP341 published vectors
against the reference and the generator; a block's weight and counts for
any seed; the cell ``btc-node.ibd-taproot`` rehearsed through ``Node`` from
its own files, its three controls, and the sizing rule of every finite
backlog (PR 40's, here so that tier-1 guards it).  And the handshake: a
segwit network's node refuses a peer without the witness service bit."""

import asyncio
import importlib
import json
import time

import pytest

from chipbench import gen_btc, harness, prevouts_btc, reference_btc, secp
from chipbench import wirefmt as w
from chipbench.tests.rehearse import rehearse
from tests.test_bip340 import BIP340_OFFCURVE_PUB, BIP340_VECTORS

CELL = "btc-node.ibd-taproot"
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
_, _, CONFIG, TRAFFIC = harness.load_cell(CELL)
MIX = TRAFFIC["mix"]
ORACLE = prevouts_btc.Oracle()

CASES = [(kind, None) for kind in gen_btc.KINDS] + [
    (kind, adv) for adv, (kinds, _ok) in gen_btc.ADVERSARIAL.items()
    for kind in kinds]


def _txs(kind: str, adv, count: int = 3, seed: int = 42) -> dict:
    """``count`` txs ``[kind, another]``, every one adversarial under ``adv``."""
    other = "p2wpkh" if kind == "p2tr" else "p2tr"
    mix = {"pattern": [[kind, other]]}
    if adv is not None:
        mix.update(adversarial_every=1, adversarial=[adv])
    return gen_btc.gen_job({"mix": mix, "seed": seed, "first_tx": 0,
                            "count": count, "total": count})


def _prevouts(raw: bytes) -> tuple:
    tx, _ = reference_btc.parse_tx(raw)
    rows = [ORACLE(i[0], i[1]) for i in tx.ins]
    return [a for a, _ in rows], [s for _, s in rows]


def _program_native(raws: list) -> list:
    from tpunode.txextract import extract_raw
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    amounts, scripts = [], []
    for raw in raws:
        a, s = _prevouts(raw)
        amounts += a
        scripts += s
    items = extract_raw(b"".join(raws), len(raws), bch=False,
                        ext_amounts=amounts, ext_scripts=scripts)
    assert int(items.tx_unsupported.sum()) == 0
    per_sig = items.combine(verify_batch_cpu(items.to_verify_items()))
    return [tuple(per_sig[sl]) for sl in items.sig_slices()]


def _program_python(raws: list) -> list:
    from tpunode.txverify import combine_verdicts, extract_sig_items
    from tpunode.util import Reader
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.wire import Tx

    out = []
    for raw in raws:
        tx = Tx.deserialize(Reader(raw))
        amounts, scripts = _prevouts(raw)
        items, stats = extract_sig_items(
            tx, prevout_amounts=dict(enumerate(amounts)),
            prevout_scripts=dict(enumerate(scripts)))
        assert stats.unsupported == 0
        out.append(tuple(combine_verdicts(
            items, verify_batch_cpu([i.verify_item for i in items]))))
    return out


@pytest.mark.parametrize("extractor", ["native", "python"])
@pytest.mark.parametrize("kind,adv", CASES,
                         ids=[f"{k}-{a or 'valid'}" for k, a in CASES])
def test_program_reference_and_construction_agree(kind, adv, extractor):
    if extractor == "native":
        from tpunode.txextract import have_native_extract

        if not have_native_extract():
            pytest.skip("native extractor unavailable")
    out = _txs(kind, adv)
    want = [tuple(v) for v in out["expect"]]
    if adv is not None:
        assert all(v[0] is gen_btc.ADVERSARIAL[adv][1] for v in want)
        assert all(all(v[1:]) for v in want)
    else:
        assert all(all(v) for v in want)
    ref = [reference_btc.tx_verdicts(raw, ORACLE) for raw in out["raw"]]
    assert ref == want
    program = (_program_native if extractor == "native"
               else _program_python)(out["raw"])
    assert program == want
    assert [reference_btc.parse_tx(r)[0].txid for r in out["raw"]] == out["txids"]
    assert all(reference_btc.unsupported_inputs(r, ORACLE) == 0
               for r in out["raw"])


# ---- published vectors -------------------------------------------------------


@pytest.mark.parametrize("row", BIP340_VECTORS, ids=lambda r: r[1][:8])
def test_bip340_vectors_against_the_reference(row):
    _sk, pub, _aux, msg, sig, want = row
    key, m, s = bytes.fromhex(pub), bytes.fromhex(msg), bytes.fromhex(sig)
    assert reference_btc.bip340_verify(key, m, s) is want
    # and the systematic negatives: a flipped s, a flipped message, the
    # signature under the negated nonce (x(R) = r, y(R) odd)
    bad_s = s[:32] + ((int.from_bytes(s[32:], "big") + 1) % secp.N).to_bytes(32, "big")
    assert reference_btc.bip340_verify(key, m, bad_s) is False
    other = bytes(31) + b"\x01" if m == bytes(32) else bytes(32)
    assert reference_btc.bip340_verify(key, other, s) is False


def test_bip340_signing_reproduces_the_vectors_signature_equation():
    """The generator's signer under a vector's key and its nonce point:
    the signature verifies, and with ``odd_r`` only the parity check sees
    the difference."""
    sk, pub, _aux, msg, sig, _ = BIP340_VECTORS[1]
    d, P = gen_btc.even(int(sk, 16), secp.mul(int(sk, 16), secp.G))
    assert P[0] == int(pub, 16)
    k = 0xC0FFEE
    kpt = secp.mul(k, secp.G)
    m = bytes.fromhex(msg)
    for odd in (False, True):
        r, s = gen_btc.bip340_sign(d, P[0], m, k, kpt, odd_r=odd)
        blob = r.to_bytes(32, "big") + s.to_bytes(32, "big")
        key = bytes.fromhex(pub)
        assert reference_btc.bip340_verify(key, m, blob) is (not odd)
        assert reference_btc.bip340_verify(
            key, m, blob, reference_btc.Checks(parity=False)) is True


def test_bip340_key_with_no_point_is_invalid():
    key = bytes.fromhex(BIP340_OFFCURVE_PUB)
    assert reference_btc.lift_x(int.from_bytes(key, "big")) is None
    assert reference_btc.bip340_verify(key, bytes(32), bytes(64)) is False
    x = gen_btc.x_with_no_point(int(BIP340_VECTORS[0][1], 16))
    assert reference_btc.lift_x(x) is None


def test_bip341_taptweak_vector():
    """BIP341 wallet test vectors, ``scriptPubKey[0]`` (no script tree): the
    internal key's tweak and tweaked output key, through the generator's
    tagged hash, ``lift`` and table multiplication.  (The BIP's
    ``keyPathSpending`` transaction could not be reproduced offline; the
    BIP341 digest is pinned by the program's own ``sighash.bip341_sighash``
    in the parametrised test above and in the next one.)"""
    internal = bytes.fromhex(
        "d6889cb081036e0faefa3a35157ad71086b123b2b144b649798b494c300a961d")
    tweak = gen_btc.tagged_hash("TapTweak", internal)
    assert tweak.hex() == (
        "b86e7be8f39bab32a6f2c0443abbc210f0edac0e2c53d501b36b64437d9c6c70")
    P = reference_btc.lift_x(int.from_bytes(internal, "big"))
    t = int.from_bytes(tweak, "big")
    Q = secp.add(P, gen_btc.mul_g(t))
    assert gen_btc.mul_g(t) == secp.mul(t, secp.G)
    assert Q[0].to_bytes(32, "big").hex() == (
        "53a1f6e454df1aa2776a2814a721372d6258050de330b3c6d10ee8f4e0dda343")


@pytest.mark.parametrize("hashtype", [0x00, 0x01, 0x02, 0x03, 0x81, 0x82, 0x83])
def test_the_references_bip341_digest_equals_the_programs(hashtype):
    """Every hash type, with and without an annex and a tapleaf, on a
    generated tx: the plain reference's digest against
    ``tpunode.sighash.bip341_sighash`` (which tests/test_taproot.py pins)."""
    from tpunode.sighash import bip341_sighash, tapleaf_hash
    from tpunode.util import Reader
    from tpunode.wire import Tx

    raw = _txs("p2tr_script", None, count=1)["raw"][0]
    tx, _ = reference_btc.parse_tx(raw)
    amounts, scripts = _prevouts(raw)
    ptx = Tx.deserialize(Reader(raw))
    leaf_script = tx.wits[0][1]
    for annex in (None, b"\x50\x01\x02"):
        for leaf in (None, tapleaf_hash(leaf_script)):
            for index in (0, 1):
                got = reference_btc.bip341_digest(
                    tx, index, hashtype, amounts, scripts, annex, leaf)
                want = bip341_sighash(ptx, index, amounts, scripts, hashtype,
                                      annex, leaf_hash=leaf)
                assert int.from_bytes(got, "big") == want


# ---- the block ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
def test_a_block_is_full_and_its_counts_are_the_same_for_any_seed(seed):
    per = gen_btc.txs_that_fit(MIX, TRAFFIC["block"]["max_weight"])
    assert per == 4376 and per % len(MIX["pattern"]) == 0
    tot = gen_btc.totals(MIX, per)
    assert (tot["inputs"], tot["sigs"], tot["items"]) == (8752, 9299, 10393)
    assert tot["items.bip340"] == 4923 and tot["items.ecdsa"] == 5470
    assert tot["sigs.bip340"] / tot["sigs"] == pytest.approx(9 / 17)
    assert tot["items.bip340"] / tot["items"] == pytest.approx(9 / 19)
    assert (tot["in.p2tr"] + tot["in.p2tr_script"]) / tot["inputs"] == 9 / 16
    out = gen_btc.blocks_job({
        "mix": MIX, "seed": seed, "first_tx": 0, "count": per,
        "total": per, "txs_per_block": per, "first_height": 1})
    (weight,) = out["block_weights"]
    assert 0.997 * 4_000_000 <= weight <= 4_000_000
    # one turn of the pattern more would not fit
    assert weight + weight / (per / 8) > 4_000_000
    (_merkle, cb_txid, body), (offs,) = out["bodies"][0], out["offsets"]
    assert len(offs) == per + 1 and offs[-1] == len(body)
    assert 1_550_000 < len(body) < 1_650_000
    # the body parses back: the coinbase (commitment and all), then the txs
    n, off = w.read_varint(body, 0)
    assert n == per + 1
    cb, end = reference_btc.parse_tx(body, off)
    assert cb.txid == cb_txid and end == offs[0]
    assert cb.outs[1][1][:6] == bytes.fromhex("6a24aa21a9ed")
    assert cb.wits == [[bytes(32)]]
    for i in (0, 1, 3, 7, per - 1):  # a sample of the cuts
        raw = body[offs[i]:offs[i + 1]]
        assert reference_btc.parse_tx(raw)[0].txid == out["txids"][i]
    assert sum(len(v) for v in out["expect"]) == tot["sigs"]
    assert len(out["adversarial"]) == per // MIX["adversarial_every"] == 34
    assert sum(not all(v) for v in out["expect"]) == len(
        [k for k in out["adversarial"].values() if k != "high_s"])


def test_totals_in_closed_form_equal_totals_by_walking():
    import collections

    for count in (8, 100, 1031):
        walked = collections.Counter()
        for t in range(count):
            for kind in MIX["pattern"][t % len(MIX["pattern"])]:
                _f, sigs, items, algo, _w = gen_btc.KINDS[kind]
                walked["inputs"] += 1
                walked["sigs"] += sigs
                walked["items"] += items
                walked["items." + algo] += items
        plan = gen_btc.plan_adversarial(MIX, 9, 0, count, count)
        tot = gen_btc.totals(MIX, count)
        assert {k: tot[k] for k in walked} == dict(walked)
        assert sum(v for k, v in tot.items() if k.startswith("adv.")) == len(plan)


# ---- the cell ----------------------------------------------------------------


def _compared(res: dict) -> dict:
    return {k: v["value"] for k, v in res["compared"].items()}


def test_the_cell_rehearses_correct_through_node_from_its_own_files(capfd):
    res = rehearse(CELL)
    assert res["correct"] is True and res["failed"] == 0, _compared(res)
    assert res["attempted"] > 2000 and res["rehearsal"] is True
    assert set(res["metrics"]) == {"sigs_per_s", "host_cpu_ms_per_ksig", "setup_s"}
    got = _compared(res)
    for name in ("inputs_the_extractor_called_unsupported",
                 "prevout_rows_no_source_answered",
                 "device_items_by_algorithm_off_the_mix",
                 "blocks_asked_for_without_their_witnesses",
                 "reference_vs_program", "reference_vs_construction"):
        assert got[name] == 0, name
    out = capfd.readouterr().out
    line = next(l for l in out.splitlines() if '"per_layer_untraced"' in l)
    layer = json.loads(line)
    assert layer["extract.unsupported_share"] == 0.0
    assert 45.0 < layer["extract.bip340_share"] < 47.5
    assert layer["extract.lift_us_per_key"] > 0
    assert layer["extract.digest_us_per_input"] > 0


def test_a_peer_that_serves_blocks_stripped_of_witnesses_reads_not_correct(
        monkeypatch):
    """The peer says it has the witness bit and serves every eighth block
    without its witnesses: every segwit input of those reads unsupported,
    and the run is not correct."""
    from chipbench import peers_btc
    from chipbench.peers import Remote

    def offer_stripped(self, headers, hashes, blocks):
        served = dict(blocks)  # the driver keeps what it made
        for h in hashes[5::8]:
            payload = blocks[h][24:]
            n, off = w.read_varint(payload, 80)
            raws = []
            for _ in range(n):
                _tx, end = reference_btc.parse_tx(payload, off)
                raws.append(reference_btc.stripped(payload[off:end]))
                off = end
            served[h] = w.frame(self.magic, "block", payload[:80] + w.varint(n)
                                + b"".join(raws))
        Remote.offer(self, headers, hashes, served)

    monkeypatch.setattr(peers_btc.WitnessRemote, "offer", offer_stripped)
    res = rehearse(CELL)
    got = _compared(res)
    assert res["correct"] is False
    assert got["inputs_the_extractor_called_unsupported"] > 0
    assert got["verdicts_differing_from_construction"] > 0


class _Ev:
    def __init__(self, txid, verdicts):
        self.txid, self.verdicts = txid, verdicts
        self.valid, self.error = all(verdicts), None


def _decide(checks: dict) -> dict:
    """``correct`` for the verdict stream a reference with ``checks`` would
    have produced, in the program's place, over 128 txs holding every
    adversarial kind."""
    bench, wl, cfg, tr = harness.load_cell(CELL)
    tr = dict(tr, reference_sample_txs=40)
    ctx = harness.Ctx(wl, bench, cfg, tr, 5, 1.0, False, harness.Rehearsal(),
                      time.monotonic())
    mix = dict(MIX, adversarial_every=8)
    out = gen_btc.gen_job({"mix": mix, "seed": 5, "first_tx": 0,
                           "count": 128, "total": 128})
    offered = harness.Offered(dict(zip(out["txids"], out["expect"])),
                              {t: 1 for t in out["txids"]},
                              dict(zip(out["txids"], out["raw"])), {})
    sink = harness.Sink()
    weak = reference_btc.Checks(**checks)
    for txid, raw in zip(out["txids"], out["raw"]):
        sink.add(_Ev(txid, reference_btc.tx_verdicts(raw, ORACLE, weak)), 0.5)
    harness.start_pool(ctx)
    try:
        correct, attempted, failed, compared = asyncio.run(
            harness.decide_correct(ctx, offered, sink, (0.0, 1.0), []))
    finally:
        ctx.pool.terminate()
        ctx.pool.join()
    assert attempted == 128 and (failed == 0) == correct
    return {"correct": correct, "failed": failed, "plan": out["adversarial"]}


@pytest.mark.parametrize("name,checks,kind", [
    ("sound", {}, None),
    ("skips_the_parity_check", {"parity": False}, "bip340_odd_r"),
    ("leaves_amounts_out_of_the_bip341_digest", {"amounts": False},
     "p2tr_amount_off_by_one"),
])
def test_correct_sees_a_weakened_verifier(name, checks, kind):
    got = _decide(checks)
    if kind is None:
        assert got["correct"] is True
        return
    assert got["correct"] is False
    if name.startswith("skips"):
        # exactly the txs of that kind are misjudged
        assert got["failed"] == sum(k == kind for k in got["plan"].values())
    else:
        # every BIP341 signature was made over the amounts: all read invalid
        assert got["failed"] >= sum(k == kind for k in got["plan"].values())


# ---- sizing: every finite backlog holds to 1.5 x its measured rate ------------

BACKLOG = next(m for m in BENCH["per_layer"]
               if m["name"] == "backlog.left_share")["workloads"]
ROOM = 1.5


def test_the_backlog_cells_are_six():
    # and PR 44's seventh, appended (tests/test_chain_cell.py holds it to
    # the same rule)
    assert sorted(BACKLOG) == [
        "bch-32mb.blocks", "bch-32mb.single", "bch-chain.ibd-recent",
        "bch-node.ibd", "bch-utxo.ibd-spend", "bch-wan.ibd-faults", CELL]
    for wl in BENCH["workloads"]:
        traffic = harness.load_json(harness.ROOT, "chipbench", "traffic",
                                    wl["traffic"] + ".json")
        driver = importlib.import_module(
            "chipbench.drivers." + traffic["driver"])
        assert hasattr(driver, "backlog") == (wl["name"] in BACKLOG), wl
        assert ("backlog" in traffic) == (wl["name"] in BACKLOG), wl


@pytest.mark.parametrize("cell", BACKLOG)
def test_window_and_capture_hold_to_one_and_a_half_times_the_measured_rate(cell):
    _, _wl, _, traffic = harness.load_cell(cell)
    driver = importlib.import_module("chipbench.drivers." + traffic["driver"])
    seconds = BENCH["run_seconds"]
    b = driver.backlog(traffic, seconds)
    note = traffic["backlog"]["note"]
    assert b["measured"] == traffic["backlog"]["measured_sigs_per_s"]
    assert "ledger" in note or "chip run" in note  # the rate names its origin
    assert b["window_holds_to"] >= ROOM * b["measured"], b
    assert b["capture_holds_to"] >= b["window_holds_to"]
    assert b["blocks"] >= traffic["backlog"].get("min_blocks", 1)
    # the block count is a function of the file and the seconds alone
    assert driver.backlog(traffic, seconds) == b
    assert driver.backlog(traffic, seconds / 2)["blocks"] <= b["blocks"]


def test_the_new_cells_backlog_by_hand():
    b = importlib.import_module("chipbench.drivers.ibd_btc").backlog(TRAFFIC, 40)
    per = gen_btc.totals(MIX, 4376)["sigs"]
    assert b["sigs"] == b["blocks"] * per
    steady = TRAFFIC["steady_until_share"] * b["sigs"]
    assert b["window_holds_to"] == pytest.approx(steady / 43.0)
    assert b["capture_holds_to"] == pytest.approx(steady / 39.0)
    assert TRAFFIC["ramp_seconds"] == 3.0 and TRAFFIC["trace_seconds"] == 4.0
    assert CONFIG["node"]["ibd"] == {"batch_blocks": 16, "tick_interval": 0.02}
    bch = harness.load_json(harness.ROOT, "chipbench", "configs", "bch-node.json")
    assert CONFIG["verify"] == bch["verify"]
    assert CONFIG["guarantees"] == bch["guarantees"]
    assert CONFIG["reference"] == "reference_btc"


# ---- the handshake -----------------------------------------------------------


@pytest.mark.asyncio
@pytest.mark.parametrize("witness_bit", [True, False])
async def test_a_segwit_networks_node_refuses_a_peer_without_the_witness_bit(
        witness_bit):
    from tests.fakenet import dummy_peer_connect, poll_until
    from tests.fixtures import all_blocks
    from tpunode.actors import Publisher
    from tpunode.events import events
    from tpunode.node import Node, NodeConfig
    from tpunode.params import BTC_REGTEST, NODE_NETWORK, NODE_WITNESS
    from tpunode.store import MemoryKV

    services = NODE_NETWORK | (NODE_WITNESS if witness_bit else 0)
    cfg = NodeConfig(
        net=BTC_REGTEST, store=MemoryKV(), pub=Publisher(name="segwit-bit"),
        peers=["[::1]:18444"],
        connect=lambda sa: dummy_peer_connect(
            BTC_REGTEST, all_blocks(), services=services))
    seq = events.seq()

    def handshakes():
        return [e for e in events.tail_since(seq, 200)
                if e["type"] == "peer.handshake"]

    async with Node(cfg) as node:
        if witness_bit:
            await poll_until(lambda: node.peer_mgr.get_peers(), 10, "a peer online")
            assert not any(e.get("reason") == "no-segwit" for e in handshakes())
        else:
            await poll_until(
                lambda: any(e.get("reason") == "no-segwit" for e in handshakes()),
                10, "the refusal")
            refused = next(e for e in handshakes()
                           if e.get("reason") == "no-segwit")
            assert refused["ok"] is False
            assert not node.peer_mgr.get_peers()


def test_bch_networks_take_a_peer_without_the_witness_bit():
    from tpunode.params import NETWORKS

    assert [n for n, net in NETWORKS.items() if net.segwit] == [
        "btc", "btctest", "btcreg"]
