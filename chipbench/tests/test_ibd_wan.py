"""The ``ibd_wan`` driver's cell (``bch-wan.ibd-faults``) at a tiny size on
the CPU (the traffic file's ``rehearsal`` section: thin uplinks, the faults
at a tenth of their moments), both faults inside a 4 s window: ``correct:
true``.  Controls that
read ``correct: false``: a node that verifies a block it is sent twice, an
emulator with no round-trip time, one that ignores the uplink, a fault
schedule that falls after the window.  And ``reference_wan.py`` against a
three-block example worked by hand.

The chain's tiny size is the same section's ``traffic``.
"""

import json

import pytest

from chipbench import peers_wan, reference_wan
from chipbench.tests.rehearse import rehearse, tiny

CELL = "bch-wan.ibd-faults"


# a fault schedule as a run on the chip has it (8 s, 20 s): after a test's window
LATE = {"rehearsal": {"fault_scale": 1.0}}


def _lines(capfd, kind: str) -> list:
    return [row for row in map(json.loads, (
        ln for ln in capfd.readouterr().out.splitlines()
        if ln.startswith('{"line": "' + kind + '"')))]


def _compared(capfd) -> dict:
    return {row["name"]: row["value"] for row in _lines(capfd, "compared")}


NETWORK = ("blocks_sooner_than_one_rtt_after_their_request",
           "pieces_sooner_than_the_uplink_allows", "blocks_nobody_asked_for",
           "seconds_over_the_uplink", "fault_outside_the_window",
           "staller_block_pieces_after_its_moment", "reset_peer_not_back",
           "verdict_gaps_over_the_stall_timeout_plus_1s",
           "served_blocks_not_a_prefix", "utxo_watermark_behind_last_verified",
           "verdicts_beyond_one_per_tx_offered", "verdicts_missing")


def test_ibd_past_a_stall_and_a_reset_reads_correct(capfd):
    res = rehearse(CELL, seconds=4.0, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 1000 < m["ibd.stall_recover_ms"] < 1600  # the timeout, a tick, a peer
    assert 500 < m["peer.reconnect_ms"] < 1500  # the first backoff, a handshake
    assert 0 < m["ibd.rerequested_share"] < 50 and m["ibd.duplicate_share"] == 0
    assert 0 <= m["ibd.head_wait_share"] < 100 and m["ibd.refetch_share"] == 0
    assert 0 < m["ibd.longest_gap_s"] < 2.0 and m["wan.late_p99_ms"] >= 0
    out = capfd.readouterr().out
    rows = {r["name"]: r["value"] for r in map(json.loads, (
        ln for ln in out.splitlines() if ln.startswith('{"line": "compared"')))}
    assert all(rows[name] == 0 for name in NETWORK)
    wan = next(json.loads(ln) for ln in out.splitlines()
               if ln.startswith('{"line": "wan"'))
    assert wan["stalls"] >= 1 and wan["connections"][0] == 2
    assert sum(n > 0 for n in wan["blocks_by_peer"]) >= 6


def test_a_node_that_verifies_a_block_twice_reads_not_correct(
        monkeypatch, capfd):
    """Every peer reads every 5th hash of a getdata twice, and sends that
    block twice.  The node drops the second copy (counted); with the drop
    switched off it verifies it."""
    from tpunode.node import Node

    plain = peers_wan.w.parse_inv
    seen = []

    def every_fifth_twice(payload):
        out = []
        for inv in plain(payload):
            seen.append(inv)
            out += [inv, inv] if len(seen) % 5 == 0 else [inv]
        return out

    monkeypatch.setattr(peers_wan.w, "parse_inv", every_fifth_twice)
    res = rehearse(CELL, seconds=4.0)
    assert res["correct"] is True
    dup = _lines(capfd, "per_layer_untraced")[0]["ibd.duplicate_share"]
    assert dup > 0
    monkeypatch.setattr(Node, "_block_taken", lambda self, h: False)
    res = rehearse(CELL, seconds=4.0)
    assert res["correct"] is False
    assert _compared(capfd)["verdicts_beyond_one_per_tx_offered"] > 0


@pytest.mark.parametrize("fault,fails", [
    ("no round-trip time", "blocks_sooner_than_one_rtt_after_their_request"),
    ("ignores the uplink", "pieces_sooner_than_the_uplink_allows"),
])
def test_an_emulator_that_is_no_link_reads_not_correct(
        monkeypatch, capfd, fault, fails):
    plain = peers_wan.WanRemote.__init__

    def init(self, net, link, piece=peers_wan.PIECE):
        plain(self, net, link, piece)
        if fault == "no round-trip time":
            self.rtt = 0.0
        else:
            self.rate *= 1000

    monkeypatch.setattr(peers_wan.WanRemote, "__init__", init)
    res = rehearse(CELL, seconds=4.0)
    assert res["correct"] is False and res["failed"] == 0  # verdicts are right
    out = _compared(capfd)
    assert out[fails] > 0
    if fault == "ignores the uplink":
        assert out["seconds_over_the_uplink"] > 0


def test_a_fault_schedule_after_the_window_reads_not_correct(capfd):
    res = rehearse(CELL, seconds=4.0,
                   traffic=dict(tiny("ibd-faults"), **LATE))
    assert res["correct"] is False and res["failed"] == 0
    out = _compared(capfd)
    assert out["fault_outside_the_window"] == 2
    assert out["reset_peer_not_back"] == 1


def test_the_driver_ends_at_once_on_a_program_without_a_stall_timeout(
        monkeypatch):
    from tpunode.ibd import IbdConfig

    fields = dict(IbdConfig.__dataclass_fields__)
    del fields["stall_timeout"]
    monkeypatch.setattr(IbdConfig, "__dataclass_fields__", fields)
    with pytest.raises(SystemExit, match="no stall timeout"):
        rehearse(CELL)


# -- reference_wan.py against a hand-worked example ---------------------------
#
# One link: rtt 100 ms, 0.8 Mbit/s = 100,000 bytes a second.  Connection 1 is
# accepted at t = 0.  A getdata for blocks A, B, C (30,000 bytes each, pieces
# of 20,000) arrives at t = 1.0:
#   A may start at 1.1; its pieces end at byte 20,000 and 30,000:
#     not before 1.1 + 0.2 = 1.3 and 1.1 + 0.3 = 1.4
#   B follows A: pieces not before 1.4 + 0.2 = 1.6 and 1.7
#   C follows B: 1.9 and 2.0
# In whole seconds from the first byte (t0 = 1.1): [1.1, 2.1) carries all
# 90,000 bytes at exactly the link's rate: not over.

A, B, C = b"A" * 32, b"B" * 32, b"C" * 32
LINK = {"rtt_ms": 100, "uplink_mbit_s": 0.8}


def _log(times: dict) -> list:
    log = [("accept", 0.0, 1), ("request", 1.0, 1, "getdata", (A, B, C))]
    for key in (A, B, C):
        t1, t2 = times[key]
        log.append(("piece", t1, 1, "block", key, 20000, 20000, 30000))
        log.append(("piece", t2, 1, "block", key, 10000, 30000, 30000))
    return log


ON_TIME = {A: (1.3, 1.4), B: (1.6, 1.7), C: (1.9, 2.0)}


@pytest.mark.parametrize("times,want", [
    (ON_TIME, {}),
    # everything 50 ms late: a link may always be slower
    ({k: (a + 0.05, b + 0.05) for k, (a, b) in ON_TIME.items()}, {}),
    # A's first piece 150 ms after the request is over one rtt, but its
    # 20,000 bytes cannot have left by then
    ({**ON_TIME, A: (1.15, 1.4)}, {"pieces_sooner_than_the_uplink_allows": 1}),
    # A's first piece 50 ms after the request: sooner than one rtt, too
    ({**ON_TIME, A: (1.05, 1.4)},
     {"blocks_sooner_than_one_rtt_after_their_request": 1,
      "pieces_sooner_than_the_uplink_allows": 1}),
    # B and C right behind A, as if the uplink were not there: their four
    # pieces are early (no second is over: the same 90,000 bytes fall in
    # the one second from 1.1)
    ({A: (1.3, 1.4), B: (1.45, 1.5), C: (1.55, 1.6)},
     {"pieces_sooner_than_the_uplink_allows": 4}),
])
def test_the_networks_reference_on_three_blocks_by_hand(times, want):
    got = reference_wan.check_link(LINK, _log(times), 0.0, (0.0, 10.0))
    assert {k: v for k, v in got.items() if v} == want


def test_the_networks_reference_counts_seconds_over_the_uplink():
    """Six blocks in the second three fit in."""
    log = [("accept", 0.0, 1),
           ("request", 1.0, 1, "getdata", tuple(bytes([k]) * 32 for k in range(6)))]
    for k in range(6):
        log.append(("piece", 1.4 + 0.1 * k, 1, "block", bytes([k]) * 32,
                    30000, 30000, 30000))
    got = reference_wan.check_link(LINK, log, 0.0, (0.0, 10.0))
    assert got["seconds_over_the_uplink"] == 1
    assert got["pieces_sooner_than_the_uplink_allows"] == 5


@pytest.mark.parametrize("kind,log_tail,want", [
    # the staller cuts B at 5.0 s, inside the window, and sends no more
    ("stall", [("piece", 5.0, 1, "block", B, 10000, 10000, 30000)], {}),
    # ... it completes B after its moment
    ("stall", [("piece", 5.0, 1, "block", B, 20000, 20000, 30000),
               ("piece", 5.2, 1, "block", B, 10000, 30000, 30000)],
     {"fault_outside_the_window": 1,
      "staller_block_pieces_after_its_moment": 2}),
    # ... it never stalls
    ("stall", [], {"fault_outside_the_window": 1}),
    # the reset peer cuts B at 5.0 s and is accepted again at 5.6
    ("reset", [("piece", 5.0, 1, "block", B, 10000, 10000, 30000),
               ("accept", 5.6, 2)], {}),
    # ... it is not seen again
    ("reset", [("piece", 5.0, 1, "block", B, 10000, 10000, 30000)],
     {"reset_peer_not_back": 1}),
    # ... it cuts after the window closed
    ("reset", [("piece", 9.5, 1, "block", B, 10000, 10000, 30000),
               ("accept", 9.9, 2)], {"fault_outside_the_window": 1}),
])
def test_the_networks_reference_on_the_faults(kind, log_tail, want):
    link = dict(LINK, fault={"kind": kind, "at_s": 2.0})  # armed at 2: 4.0
    log = [("accept", 0.0, 1), ("request", 1.0, 1, "getdata", (A,)),
           ("piece", 1.4, 1, "block", A, 30000, 30000, 30000),
           ("request", 4.5, 1, "getdata", (B,))] + log_tail
    got = reference_wan.check_link(link, log, 2.0, (2.0, 9.0))
    assert {k: v for k, v in got.items() if v} == want
