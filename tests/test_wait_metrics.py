"""The per-layer metrics that read the waits (ISSUE 24): each new reader on
a hand-made ``Reading`` — with ``None`` where its input is missing, as at a
parent commit that has no such span — and every ``per_layer`` entry of
``BENCHMARK.json`` against its metric file and its reader."""

import importlib

import pytest

from chipbench import harness
from chipbench.readers import (
    idle_gap_share,
    span_self_ms_per_count,
    span_share_of_window,
)

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = {wl["name"] for wl in BENCH["workloads"]}
WAITS = (
    "sched.starved_share", "sched.slot_wait_share",
    "sched.linger_ms_per_lane", "engine.hop_ms_per_lane",
    "engine.deliver_ms_per_lane", "transfer.on_cpu_share",
    "prep.on_cpu_share", "device.idle_share.starved",
    "device.idle_share.unnamed",
)
COUNTER_BASED = WAITS[:7]
CONNECT = ("utxo.connect_ms_per_block", "utxo.connect_cpu_ms_per_block")

# one window of a program that has the spans: 10 lanes in 4 s
COUNTERS = {
    "sched.lanes": 10.0,
    "span.sched.starved.seconds": 3.0, "span.sched.starved.count": 12.0,
    "span.sched.slot_wait.seconds": 0.0, "span.sched.slot_wait.count": 10.0,
    "span.sched.linger.seconds": 0.25, "span.sched.linger.count": 10.0,
    "span.verify.lane.seconds": 1.0, "span.verify.lane.count": 10.0,
    "span.verify.dispatch.seconds": 0.6, "span.verify.dispatch.count": 10.0,
    "span.verify.deliver.seconds": 0.1, "span.verify.deliver.count": 10.0,
    "span.verify.transfer.seconds": 0.2,
    "span.verify.transfer.cpu_seconds": 0.05,
    "span.verify.prepare.seconds": 0.1,
    "span.verify.prepare.cpu_seconds": 0.09,
}
TRACE = {"window_s": 2.0, "busy_s_by_chip": [0.45], "kernels": {},
         "breakdown": {"idle_gaps": [
             ["sched.starved", 1.2], ["verify.transfer", 0.3],
             ["none", 0.05]]}}
# the same window at a commit without them
PARENT = {k: v for k, v in COUNTERS.items()
          if not k.startswith(("span.sched.", "span.verify.lane",
                               "span.verify.deliver"))
          and not k.endswith(".cpu_seconds")}


def reading(counters=COUNTERS, trace=TRACE, window_s=4.0):
    return harness.Reading(dict(counters), window_s, trace, {}, {})


def test_span_share_of_window():
    assert span_share_of_window.read(
        reading(), span="sched.starved") == pytest.approx(75.0)
    # a wait that never had to be waited reads 0, not nothing
    assert span_share_of_window.read(reading(), span="sched.slot_wait") == 0.0
    assert span_share_of_window.read(
        reading(PARENT), span="sched.starved") is None
    assert span_share_of_window.read(
        reading(window_s=0.0), span="sched.starved") is None


def test_span_self_ms_per_count():
    args = dict(span="verify.lane", children=["verify.dispatch",
                                              "verify.deliver"],
                per=["sched.lanes"])
    assert span_self_ms_per_count.read(
        reading(), **args) == pytest.approx(30.0)  # (1.0 - 0.6 - 0.1) s / 10
    assert span_self_ms_per_count.read(reading(PARENT), **args) is None
    no_lanes = dict(COUNTERS, **{"sched.lanes": 0.0})
    assert span_self_ms_per_count.read(reading(no_lanes), **args) is None
    # a child the window never saw takes nothing away
    assert span_self_ms_per_count.read(
        reading(), span="verify.lane", children=["verify.absent"],
        per=["sched.lanes"]) == pytest.approx(100.0)


def test_idle_gap_share():
    assert idle_gap_share.read(
        reading(), span="sched.starved") == pytest.approx(60.0)
    assert idle_gap_share.read(reading(), span="none") == pytest.approx(2.5)
    # the program has the span, the breakdown's ten largest do not: 0
    assert idle_gap_share.read(reading(), span="sched.linger") == 0.0
    # no trace (an untraced run); no such span (the parent commit)
    assert idle_gap_share.read(
        reading(trace=None), span="sched.starved") is None
    assert idle_gap_share.read(reading(PARENT), span="sched.starved") is None
    # what no span covers can be read at any commit
    assert idle_gap_share.read(
        reading(PARENT), span="none") == pytest.approx(2.5)
    all_named = dict(TRACE, breakdown={"idle_gaps": [["sched.starved", 1.0]]})
    assert idle_gap_share.read(reading(trace=all_named), span="none") == 0.0


@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_entry_has_its_metric_file_and_its_reader(entry):
    spec = harness.load_json(harness.ROOT, "chipbench", "metrics",
                             entry["name"] + ".json")
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert set(entry["workloads"]) <= CELLS
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    # the reader takes the file's arguments; on an empty window it returns
    # a number or nothing, and does not raise
    empty = harness.Reading({}, 1.0, None, {}, {})
    out = reader.read(empty, **spec.get("args", {}))
    assert out is None or isinstance(out, float)


def test_the_nine_wait_metrics_are_listed_in_every_cell():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in WAITS:
        assert set(by_name[name]["workloads"]) == CELLS, name
        assert by_name[name]["moves"] == "sigs_per_s"
    # appended: what was there before them is where it was, and what
    # later PRs added comes after them
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(WAITS[0])
    assert names[first:first + len(WAITS)] == list(WAITS)
    after = names[first + len(WAITS):]
    assert after[:len(CONNECT)] == list(CONNECT)
    # PR 27's, appended in their turn, then PR 28's, PR 30's, PR 31's,
    # PR 32's, PR 36's seven, PR 37's two, PR 38's sixteen ("node loop"),
    # PR 39's two ("host prep": what a lane's transfer is, by counters):
    # membership and order among the entries named here, wherever a later
    # PR appends its own (ISSUE 42; the list's END is nobody's to pin)
    loop = [m["name"] for m in BENCH["per_layer"] if m["layer"] == "node loop"]
    assert len(loop) == 16
    at = after.index(loop[0])
    assert after[at:at + 16] == loop
    wan = ["ibd.head_wait_share", "ibd.stall_recover_ms",
           "ibd.rerequested_share", "ibd.duplicate_share", "ibd.longest_gap_s",
           "peer.reconnect_ms", "wan.late_p99_ms"]
    named = [
        "reuse.hit_share", "reuse.ms_per_block", "reuse.cpu_ms_per_block",
        "tip.relay_verdict_p50_ms", "tip.block_verdict_p50_ms",
        "open.late_p99_ms", "open.verdict_p99_ms", "commit.ms_per_ktx",
        "resolve.us_per_input", "resolve.oracle_share",
        "utxo.lookup_us_per_row", "utxo.hit_share", "resolve.missing_share",
        "utxo.snapshot_load_s", "utxo.entries", "store.rss_mb",
        "store.compactions_in_window", "stream.early_share", *wan,
        "sched.full_cut_share", "kernel.slots_per_item", *loop,
        "transfer.bytes_per_slot", "transfer.calls_per_lane"]
    assert after[len(CONNECT):len(CONNECT) + len(named)] == named
    # ... PR 36's only their own cell lists: no other cell's run reads them
    for name in wan:
        assert by_name[name]["workloads"] == ["bch-wan.ibd-faults"], name
    # PR 37's move the CPU metric, so every cell that reports it lists them
    cpu = next(m for m in BENCH["end_to_end"]
               if m["name"] == "host_cpu_ms_per_ksig")["workloads"]
    # ... and PR 39's likewise
    for name in ("sched.full_cut_share", "kernel.slots_per_item",
                 "transfer.bytes_per_slot", "transfer.calls_per_lane"):
        assert by_name[name]["workloads"] == cpu, name
        assert by_name[name]["moves"] == "host_cpu_ms_per_ksig"


def test_the_two_connect_metrics_read_the_utxo_connect_span():
    """ISSUE 26: ``utxo.connect`` over the blocks connected, wall and CPU,
    in the two cells that connect blocks; 0 at a commit without the span
    (``span_ms_per_count`` / ``counter_ratio`` cannot tell), nothing where
    no block was connected."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in CONNECT:
        entry = by_name[name]
        # the cells that connect blocks: PR 27 appended the tip cell,
        # PR 31 its two beside their siblings, PR 36 the IBD from a network,
        # PR 42 the BTC node's IBD, PR 44 the IBD of a chain that spends
        # its own outputs, PR 48 the tip under unconfirmed chains
        assert entry["workloads"] == ["bch-node.ibd", "bch-32mb.blocks",
                                      "bch-tip.tip", "bch-utxo.ibd-spend",
                                      "bch-32mb.single", "bch-wan.ibd-faults",
                                      "btc-node.ibd-taproot",
                                      "bch-chain.ibd-recent",
                                      "bch-unconf.tip-unconf"]
        assert entry["layer"] == "UTXO connect / store"
        assert entry["moves"] == "host_cpu_ms_per_ksig"
        assert (entry["unit"], entry["better"]) == ("ms/block", "lower")
    ctx = harness.Ctx(
        workload={"name": "bch-32mb.blocks"}, bench=BENCH, config={},
        traffic={}, seed=0, seconds=4.0, trace=False, rehearsal=None,
        t_start=0.0)
    window = dict(COUNTERS, **{
        "utxo.applied": 4.0, "span.utxo.connect.count": 4.0,
        "span.utxo.connect.seconds": 1.2,
        "span.utxo.connect.cpu_seconds": 0.5})
    got = harness.read_per_layer(ctx, reading(window, trace=None))
    assert got["utxo.connect_ms_per_block"]["value"] == pytest.approx(300.0)
    assert got["utxo.connect_cpu_ms_per_block"]["value"] == pytest.approx(125.0)
    parent = harness.read_per_layer(
        ctx, reading(dict(PARENT, **{"utxo.applied": 4.0}), trace=None))
    assert parent["utxo.connect_ms_per_block"]["value"] == 0.0
    assert parent["utxo.connect_cpu_ms_per_block"]["value"] == 0.0
    idle = harness.read_per_layer(ctx, reading(trace=None))
    assert not set(CONNECT) & set(idle)
    mempool = harness.read_per_layer(
        harness.Ctx(workload={"name": "bch-node.mempool"}, bench=BENCH,
                    config={}, traffic={}, seed=0, seconds=4.0, trace=False,
                    rehearsal=None, t_start=0.0),
        reading(window, trace=None))
    assert not set(CONNECT) & set(mempool)


@pytest.mark.parametrize("traced", [False, True])
def test_wait_metrics_read_through_the_harness(traced):
    """``harness.read_per_layer`` over the hand-made window: all nine in a
    traced run, the seven counter-based ones in an untraced run; at the
    parent commit the new readers leave their metrics out."""
    ctx = harness.Ctx(
        workload={"name": "bch-node.mempool"}, bench=BENCH, config={},
        traffic={}, seed=0, seconds=4.0, trace=traced, rehearsal=None,
        t_start=0.0)
    got = harness.read_per_layer(ctx, reading(trace=TRACE if traced else None))
    want = set(WAITS if traced else COUNTER_BASED)
    assert want <= set(got)
    assert not (set(WAITS) - want) & set(got)
    assert got["sched.starved_share"]["value"] == pytest.approx(75.0)
    assert got["sched.linger_ms_per_lane"]["value"] == pytest.approx(25.0)
    assert got["engine.deliver_ms_per_lane"]["value"] == pytest.approx(10.0)
    assert got["transfer.on_cpu_share"]["value"] == pytest.approx(25.0)
    assert got["prep.on_cpu_share"]["value"] == pytest.approx(90.0)
    old = harness.read_per_layer(
        ctx, reading(PARENT, trace=TRACE if traced else None))
    for name in ("sched.starved_share", "sched.slot_wait_share",
                 "engine.hop_ms_per_lane", "device.idle_share.starved"):
        assert name not in old



@pytest.mark.parametrize("cell,samples,want", [
    ("bch-tip.tip", {"verdict_ms": [float(i) for i in range(2001)],
                     "block_ms": [100.0, 140.0, 180.0],
                     "late_ms": [float(i % 100) for i in range(2000)]},
     {"reuse.hit_share": 95.0, "reuse.ms_per_block": 17.0,
      "reuse.cpu_ms_per_block": 15.0, "tip.relay_verdict_p50_ms": 1000.0,
      "tip.block_verdict_p50_ms": 140.0, "open.verdict_p99_ms": 1980.0}),
    ("bch-node.relay-open", {"verdict_ms": [float(i) for i in range(2001)],
                             "late_ms": [1.0] * 1500},
     {"open.verdict_p99_ms": 1980.0, "open.late_p99_ms": 1.0}),
    # a program without the reuse (the parent commit), or a node that
    # looked nothing up: the three reuse metrics are left out, not 0
    ("bch-tip.tip", {}, {}),
])
def test_the_reuse_and_open_loop_metrics_read_through_the_harness(
        cell, samples, want):
    """ISSUE 27: ``node.reuse`` per looked-up block, hits over look-ups,
    and the open driver's samples, each in the cells that list it."""
    window = dict(COUNTERS)
    if samples:
        window.update({
            "node.reuse_blocks": 20.0, "node.reuse_lookups": 62000.0,
            "node.reuse_hits": 58900.0, "span.node.reuse.seconds": 0.34,
            "span.node.reuse.cpu_seconds": 0.30, "span.node.reuse.count": 20.0})
    ctx = harness.Ctx(workload={"name": cell}, bench=BENCH, config={},
                      traffic={}, seed=0, seconds=4.0, trace=False,
                      rehearsal=None, t_start=0.0)
    got = harness.read_per_layer(
        ctx, harness.Reading(window, 4.0, None, samples, {}))
    new = {k: v["value"] for k, v in got.items()
           if k.startswith(("reuse.", "tip.", "open."))}
    assert set(new) == set(want) | ({"open.late_p99_ms"} if samples else set())
    for name, value in want.items():
        assert new[name] == pytest.approx(value), name


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_commit_ms_per_ktx_reads_the_commit_span_in_every_cell(cell):
    """ISSUE 28: open time of ``node.commit`` per 1000 transactions handed
    to the engine (``node.verify_txs``), in every cell, traced or not; span
    and counter exist at the parent commit, so both sides of a pair read
    it.  ``commit.ms_per_block`` stays the IBD cells'."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    entry = by_name["commit.ms_per_ktx"]
    assert set(entry["workloads"]) == CELLS
    assert (entry["layer"], entry["moves"]) == ("verdict publication",
                                                "sigs_per_s")
    assert (entry["unit"], entry["better"]) == ("ms/ktx", "lower")
    ibd_cells = ["bch-node.ibd", "bch-utxo.ibd-spend", "bch-wan.ibd-faults",
                 "btc-node.ibd-taproot",  # PR 42's, appended in its turn
                 "bch-chain.ibd-recent"]  # and PR 44's
    assert by_name["commit.ms_per_block"]["workloads"] == ibd_cells
    ctx = harness.Ctx(workload={"name": cell}, bench=BENCH, config={},
                      traffic={}, seed=0, seconds=4.0, trace=False,
                      rehearsal=None, t_start=0.0)
    # two blocks of 66,672 txs, 0.79 s of node.commit each: 11.85 ms/ktx
    window = dict(COUNTERS, **{
        "node.verify_txs": 133344.0, "ibd.blocks": 2.0,
        "span.node.commit.seconds": 1.58, "span.node.commit.count": 8.0})
    got = harness.read_per_layer(ctx, reading(window, trace=None))
    assert got["commit.ms_per_ktx"]["unit"] == "ms/ktx"
    assert got["commit.ms_per_ktx"]["value"] == pytest.approx(1.58e6 / 133344)
    assert ("commit.ms_per_block" in got) == (cell in ibd_cells)
    # a window in which no tx reached the engine: left out, not 0
    idle = harness.read_per_layer(ctx, reading(trace=None))
    assert "commit.ms_per_ktx" not in idle


HOST_CPU_CELLS = {m["name"]: set(m["workloads"]) for m in BENCH["end_to_end"]
                  if "workloads" in m}["host_cpu_ms_per_ksig"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_resolve_us_per_input_reads_the_resolve_span_in_every_cell(cell):
    """ISSUE 30: open time of ``node.resolve`` per wanted row, in every cell,
    traced or not.  The counter is new, so a commit without it (the parent)
    reports nothing rather than a false 0."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}["resolve.us_per_input"]
    assert set(entry["workloads"]) == CELLS
    assert (entry["layer"], entry["moves"]) == ("extraction", "sigs_per_s")
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "us/input", "lower", "program_span")
    ctx = harness.Ctx(workload={"name": cell}, bench=BENCH, config={},
                      traffic={}, seed=0, seconds=4.0, trace=False,
                      rehearsal=None, t_start=0.0)
    # two blocks of 133,344 wanted rows, 0.5 s of node.resolve each
    window = dict(COUNTERS, **{
        "node.resolve_rows": 266688.0, "node.resolve_oracle_calls": 266688.0,
        "span.node.resolve.seconds": 1.0, "span.node.resolve.count": 2.0,
        "span.node.resolve.cpu_seconds": 0.4})
    got = harness.read_per_layer(ctx, reading(window, trace=None))
    assert got["resolve.us_per_input"]["unit"] == "us/input"
    assert got["resolve.us_per_input"]["value"] == pytest.approx(1e6 / 266688)
    # the parent: the span laid over it or not, no row counter, no metric
    parent = {k: v for k, v in window.items() if not k.startswith("node.res")}
    assert "resolve.us_per_input" not in harness.read_per_layer(
        ctx, reading(parent, trace=None))
    assert "resolve.us_per_input" not in harness.read_per_layer(
        ctx, reading(trace=None))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_resolve_oracle_share_reads_the_two_counters(cell):
    """ISSUE 30: the share of the wanted rows that reached the embedder's
    ``prevout_lookup``.  It moves ``host_cpu_ms_per_ksig``, so it is listed
    in the cells that report that metric: every cell but ``mempool``."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}["resolve.oracle_share"]
    assert set(entry["workloads"]) == HOST_CPU_CELLS == CELLS - {
        "bch-node.mempool"}
    assert (entry["layer"], entry["moves"]) == ("extraction",
                                                "host_cpu_ms_per_ksig")
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "lower", "program_counter")
    ctx = harness.Ctx(workload={"name": cell}, bench=BENCH, config={},
                      traffic={}, seed=0, seconds=4.0, trace=False,
                      rehearsal=None, t_start=0.0)
    # a tip block's unseen 5%: 340 rows, the mempool answers 40 of them
    window = dict(COUNTERS, **{
        "node.resolve_rows": 340.0, "node.resolve_oracle_calls": 300.0,
        "span.node.resolve.seconds": 0.001})
    got = harness.read_per_layer(ctx, reading(window, trace=None))
    assert ("resolve.oracle_share" in got) == (cell in HOST_CPU_CELLS)
    if cell in HOST_CPU_CELLS:
        assert got["resolve.oracle_share"]["unit"] == "%"
        assert got["resolve.oracle_share"]["value"] == pytest.approx(
            100 * 300 / 340)
        # every row answered by the program's own sources: 0, not nothing
        own = dict(window, **{"node.resolve_oracle_calls": 0.0})
        assert harness.read_per_layer(ctx, reading(own, trace=None))[
            "resolve.oracle_share"]["value"] == 0.0
    # no wanted row in the window (or the parent commit): left out
    assert "resolve.oracle_share" not in harness.read_per_layer(
        ctx, reading(trace=None))
