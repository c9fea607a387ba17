"""chip_smoke.py — the quickest proof that tpunode still starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU
    python3 chip_smoke.py --dryrun   # anywhere: same legs, tiny, backend="cpu"

One process drives the verify path once through the entry points a user
calls, at the engine's default lane shapes (``batch_size=4096``,
``device_batch=32768``), and checks every verdict against a reference:

* env     — devices, compile-cache directory, the three native libraries
            built from ``native/*/*.cpp`` (a compiler error is a failure);
* engine  — ``VerifyEngine(VerifyConfig(backend="tpu"))``: ECDSA-only
            items in odd-sized submissions, verdicts == the C++ verifier's
            (cold compile of all four programs happens in its warmup);
* node    — ``Node`` over real TCP against a wire-speaking remote, LogKV
            with fsync, UTXO store, the IBD planner fetching a mixed-script
            BCH chain (Schnorr every 4th tx), then a mempool tail with
            corrupted signatures; its fresh engine's warmup is the warm
            pass over the persistent compile cache;
* served  — after each of the two: every item on the tpu rung, none on
            cpu/oracle, no failover, the Pallas program and not the XLA one;
* four    — with >= 4 TPU devices: one lane sharded over four chips, and
            four one-chip fleet hosts.

It fails (non-zero exit, no ``"ok": true``) the moment JAX reports no TPU;
the first failing check ends the run.  Its only children are ``make`` and
the chain-generation workers, which never import jax.  Timings printed
here are set-up and smoke timings, never rates.  Stdout is one JSON line
per leg, a ``summary`` line, and last — exactly, no other key —
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import contextlib
import json
import multiprocessing
import os
import random
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
T0 = time.monotonic()

# config 3's chain shape (benchmarks/run.py): never cut — only --blocks is
TXS_PER_BLOCK = 64
SCHNORR_EVERY = 4
JOB_BLOCKS = 25  # blocks per chain-generation job

COUNTERS = (
    "verify.tpu_items",
    "verify.cpu_items",
    "verify.oracle_items",
    "verify.failovers",
    "verify.dispatch_errors",
)


class SmokeFailure(AssertionError):
    """A smoke check did not hold (never stripped by ``python -O``)."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def note(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


# ---- chain-generation workers (spawned; never import jax) ------------------


def reference_extract(tx):
    """The Python reference extraction of one BCH tx against the synthetic
    prevout oracle: what the node's native ingest has to reproduce."""
    from benchmarks.txgen import synth_prevout
    from tpunode.txverify import extract_sig_items, wants_amount

    amounts, scripts = {}, {}
    for idx, txin in enumerate(tx.inputs):
        if wants_amount(tx, idx, True):
            amt, script = synth_prevout(txin.prevout.txid, txin.prevout.index)
            amounts[idx] = amt
            scripts[idx] = script
    return extract_sig_items(
        tx, prevout_amounts=amounts or None, bch=True,
        prevout_scripts=scripts or None,
    )


def gen_segment(job):
    """One job: ``count`` mixed-script txs from ``seed`` (every
    ``invalid_every``-th with its first signature corrupted; 0 = none) plus
    the reference totals (device items, signatures, extracted /
    non-coinbase inputs)."""
    seed, count, invalid_every = job
    from benchmarks.txgen import gen_mixed_txs

    txs = gen_mixed_txs(
        count, seed=seed, invalid_every=invalid_every,
        schnorr_every=SCHNORR_EVERY, taproot=False,
    )
    tot = collections.Counter()
    for tx in txs:
        items, st = reference_extract(tx)
        tot["items"] += len(items)
        tot["sigs"] += st.sigs
        tot["extracted"] += st.extracted
        tot["inputs"] += st.total_inputs - st.coinbase
    if "jax" in sys.modules:
        raise SmokeFailure("chain-generation worker imported jax")
    return txs, dict(tot)


# ---- helpers ---------------------------------------------------------------


class Run:
    """What every leg shares: the flags, the device identity every result
    line carries, counter snapshots."""

    def __init__(self, args):
        self.args = args
        self.dryrun = args.dryrun
        self.device: dict = {}
        self.jax_version = ""
        self.legs: dict = {}
        self.cache = collections.Counter()  # persistent-cache hits/misses

    def emit(self, leg: str, **fields) -> None:
        """One result line per leg (stdout, JSON)."""
        row = {"leg": leg, "passed": True, "device": self.device,
               "jax": self.jax_version}
        if self.dryrun:
            row["dryrun"] = True
        row.update(fields)
        self.legs[leg] = fields
        print(json.dumps(row), flush=True)

    def rung(self) -> str:
        return "cpu" if self.dryrun else "tpu"

    def verify_cfg(self, **kw):
        from tpunode.verify.engine import VerifyConfig

        if self.dryrun:  # tiny shapes, the C++ rung: debugs the harness only
            kw = {"batch_size": 64, "device_batch": 256, **kw}
        return VerifyConfig(backend=self.rung(), **kw)


def counters() -> dict:
    from tpunode.metrics import metrics

    return {name: metrics.get(name) for name in COUNTERS}


def occupancy() -> dict:
    """The lane-occupancy histogram so far: observations per bucket."""
    from tpunode.metrics import metrics

    h = metrics.histogram("verify.occupancy")
    return h.bucket_counts() if h is not None else {}


def occupancy_since(before: dict) -> dict:
    """One leg's lane-occupancy histogram."""
    return {
        le: n - before.get(le, 0)
        for le, n in occupancy().items()
        if n != before.get(le, 0)
    }


def check_served(run: Run, eng, before: dict, submitted: int) -> dict:
    """Nothing stood in for the chip: every submitted item ran on the tpu
    rung of a ready engine at the default lane shape."""
    after = counters()
    delta = {k: int(after[k] - before[k]) for k in COUNTERS}
    mine = f"verify.{run.rung()}_items"
    check(delta[mine] == submitted,
          f"{mine} moved by {delta[mine]}, submitted {submitted}")
    for name in COUNTERS:
        if name != mine:
            check(delta[name] == 0, f"{name} moved by {delta[name]}")
    stats = eng.stats()
    out = {"counters": delta, "breaker": stats["breaker"]["state"],
           "device_state": stats["device_state"],
           "device_batch": stats["device_batch"]}
    if not run.dryrun:
        check(stats["device_state"] == "ready", f"device_state {stats}")
        check(stats["device_batch"] == 32768,
              f"device_batch {stats['device_batch']}")
        check(stats["breaker"]["state"] == "ready",
              f"breaker {stats['breaker']}")
    return out


def check_programs() -> dict:
    """Which device programs this process traced since the last
    ``jax.clear_caches()``: all four Pallas ones, the XLA one never — and
    no sharded program built from the XLA kernel either."""
    from tpunode.verify import kernel, multichip, pallas_kernel

    ran = {
        "pallas": pallas_kernel._verify_blocked_jit._cache_size(),
        "xla": kernel._verify_device_jit._cache_size(),
    }
    check(ran["pallas"] >= 4 and ran["xla"] == 0, f"programs traced: {ran}")
    check(all(key[1] for key in multichip._FN_CACHE),
          "a sharded program was built from the XLA kernel")
    return ran


def compile_events(since_seq: int) -> list:
    """Per-program first-call seconds the engine warmup recorded."""
    from tpunode.events import events

    return [
        {k: e[k] for k in ("batch", "schnorr_free", "seconds")}
        for e in events.tail(64, type="verify.compile")
        if e["seq"] > since_seq
    ]


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


async def await_warmup(eng, run: Run) -> float:
    """Wait for the engine's own warmup thread; returns its seconds."""
    if run.dryrun:
        return 0.0
    t0 = eng._warmup_started
    done = await asyncio.to_thread(
        eng._warmup_done.wait, eng.cfg.warmup_timeout
    )
    check(done and eng.device_state == "ready",
          f"warmup: state={eng.device_state} error={eng.stats()['device_error']}")
    return round(time.monotonic() - t0, 3)


# ---- leg: env --------------------------------------------------------------


def leg_env(run: Run) -> str:
    import jax

    from tpunode.verify.engine import enable_compile_cache

    cache_dir = enable_compile_cache()
    check(cache_dir, "no compile-cache directory in force")
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        check(cache_dir == os.environ["JAX_COMPILATION_CACHE_DIR"],
              f"cache dir {cache_dir} ignores JAX_COMPILATION_CACHE_DIR")
    else:
        check(cache_dir == os.path.join(REPO, ".jax_cache"),
              f"cache dir {cache_dir} is not <checkout>/.jax_cache")

    def on_event(name: str, **_kw) -> None:
        if name.startswith("/jax/compilation_cache/cache_"):
            run.cache[name.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)

    t0 = time.monotonic()
    made = subprocess.run(
        ["make", "-j3", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    check(made.returncode == 0,
          f"native build failed (rc={made.returncode}):\n{made.stderr[-4000:]}")
    from tpunode import native, txextract
    from tpunode.verify import cpu_native

    check(txextract.have_native_extract(), "libtxextract did not load")
    check(cpu_native.load_native_verifier() is not None,
          "libsecp_cpu did not load")
    check(native.load_kvstore_lib() is not None, "libkvstore did not load")
    run.emit(
        "env",
        devices=[str(d) for d in jax.devices()],
        cache_dir=cache_dir,
        cache_entries=cache_entries(cache_dir),
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native={"make_seconds": round(time.monotonic() - t0, 2),
                "libs": ["libtxextract", "libsecp_cpu", "libkvstore"]},
    )
    return cache_dir


# ---- leg: engine, ECDSA-only -----------------------------------------------


async def run_engine(run: Run, cfg, items: list) -> tuple:
    """Submit ``items`` through ``verify()`` in odd-sized submissions (lanes
    pack across submission boundaries, as benchmarks/run.py config 5)."""
    from tpunode.verify.engine import VerifyEngine

    eng = VerifyEngine(cfg)
    warmup_s = await await_warmup(eng, run)
    sub = cfg.batch_size // 2 + 1
    async with eng:
        futs = [
            asyncio.ensure_future(eng.verify(items[off:off + sub]))
            for off in range(0, len(items), sub)
        ]
        got = [v for part in await asyncio.gather(*futs) for v in part]
    return eng, got, warmup_s


def ecdsa_items(run: Run) -> tuple:
    from benchmarks.common import make_triples, tile
    from tpunode.verify.cpu_native import load_native_verifier

    pool = make_triples(512, seed=run.args.seed, invalid_every=16)
    pool_expect = load_native_verifier().verify_batch(pool)
    check(0 < sum(pool_expect) < len(pool), "degenerate ECDSA pool")
    lane = 256 if run.dryrun else 32768
    n = run.args.lanes * lane + run.args.tail
    return tile(pool, n), tile(pool_expect, n)


def leg_engine(run: Run, cache_dir: str) -> tuple:
    from tpunode.events import events

    items, expect = ecdsa_items(run)
    seq0, before, occ0 = events.seq(), counters(), occupancy()
    entries0 = cache_entries(cache_dir)
    t0 = time.monotonic()
    eng, got, warmup_s = asyncio.run(run_engine(run, run.verify_cfg(), items))
    check(got == expect,
          f"{sum(a != b for a, b in zip(got, expect))} of {len(expect)} "
          "verdicts differ from the C++ verifier's")
    served = check_served(run, eng, before, len(items))
    fields = dict(
        sigs=len(items), invalid=len(expect) - sum(expect),
        seconds=round(time.monotonic() - t0, 2), warmup_seconds=warmup_s,
        first_call_seconds=compile_events(seq0),
        occupancy=occupancy_since(occ0), served=served,
        cache_entries_before=entries0,
        cache_entries_after=cache_entries(cache_dir),
        cache_events=dict(run.cache),
    )
    if not run.dryrun:
        fields["programs"] = check_programs()
        check(len(fields["first_call_seconds"]) == 4,
              f"warmup compiled {fields['first_call_seconds']}")
    run.emit("engine", **fields)
    return items, got


# ---- leg: node, the main path ----------------------------------------------


def start_chain_jobs(run: Run, pool):
    """Chain segments (all valid), then — the LAST job — the mempool tail
    with every 8th tx's first signature corrupted."""
    blocks = run.args.blocks
    jobs = []
    for k, lo in enumerate(range(0, blocks, JOB_BLOCKS)):
        n = min(JOB_BLOCKS, blocks - lo)
        jobs.append((run.args.seed + 1 + k, n * TXS_PER_BLOCK, 0))
    jobs.append((run.args.seed ^ 0x7A11, run.args.mempool_txs, 8))
    return pool.map_async(gen_segment, jobs, chunksize=1)


def tail_expectations(tail: list) -> tuple:
    """txid -> (valid, per-signature verdicts) by reference extraction and
    the C++ verifier; and the tail's device-item count."""
    from tpunode.txverify import combine_verdicts
    from tpunode.verify.cpu_native import load_native_verifier

    nv = load_native_verifier()
    want, n_items = {}, 0
    for tx in tail:
        items, _st = reference_extract(tx)
        per_sig = combine_verdicts(
            items, nv.verify_batch([i.verify_item for i in items])
        ) if items else []
        want[tx.txid] = (all(per_sig), tuple(bool(v) for v in per_sig))
        n_items += len(items)
    return want, n_items


async def remote_server(net, blocks, tail_encoded, send_tail, writers):
    """A wire-speaking remote on 127.0.0.1 with pre-encoded replies (the
    logic of benchmarks/run.py's fast_remote over real sockets, framed as
    benchmarks/soak.py does).  After ``send_tail`` it pushes the mempool
    txs unsolicited."""
    from tpunode.params import NODE_NETWORK
    from tpunode.wire import (
        HEADER_SIZE, InvType, MsgBlock, MsgGetData, MsgGetHeaders,
        MsgHeaders, MsgPing, MsgPong, MsgVerAck, MsgVersion, NetworkAddress,
        decode_message, decode_message_header, encode_message,
    )

    encoded = {b.header.hash: encode_message(net, MsgBlock(b)) for b in blocks}
    headers_reply = encode_message(
        net, MsgHeaders(tuple((b.header, len(b.txs)) for b in blocks))
    )

    async def push_tail(writer):
        await send_tail.wait()
        for enc in tail_encoded:
            writer.write(enc)
        await writer.drain()

    async def handle(reader, writer):
        writers.append(writer)
        ver = MsgVersion(
            version=70012, services=NODE_NETWORK, timestamp=int(time.time()),
            addr_recv=NetworkAddress.from_host_port("127.0.0.1", 0),
            addr_from=NetworkAddress.from_host_port(
                "127.0.0.1", 0, services=NODE_NETWORK),
            nonce=random.getrandbits(64), user_agent=b"/chipsmoke/",
            start_height=len(blocks), relay=True,
        )
        writer.write(encode_message(net, ver))
        pusher = asyncio.ensure_future(push_tail(writer))
        try:
            while True:
                raw = await reader.readexactly(HEADER_SIZE)
                hdr = decode_message_header(net, raw)
                payload = (
                    await reader.readexactly(hdr.length) if hdr.length else b""
                )
                msg = decode_message(net, hdr, payload)
                if isinstance(msg, MsgPing):
                    writer.write(encode_message(net, MsgPong(msg.nonce)))
                elif isinstance(msg, MsgVersion):
                    writer.write(encode_message(net, MsgVerAck()))
                elif isinstance(msg, MsgGetHeaders):
                    writer.write(headers_reply)
                elif isinstance(msg, MsgGetData):
                    for iv in msg.invs:
                        if iv.type in (InvType.BLOCK, InvType.WITNESS_BLOCK):
                            enc = encoded.get(iv.hash)
                            if enc is not None:
                                writer.write(enc)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            pusher.cancel()
            with contextlib.suppress(asyncio.CancelledError, ConnectionError):
                await pusher
            writer.close()

    return await asyncio.start_server(handle, "127.0.0.1", 0)


async def run_node(run: Run, blocks, tail, store_dir: str) -> dict:
    from benchmarks.txgen import synth_prevout
    from tpunode import ChainSynced, Node, NodeConfig, Publisher
    from tpunode.ibd import IbdConfig
    from tpunode.node import TxVerdict, VerifyShed
    from tpunode.params import BCH_REGTEST as net
    from tpunode.peer import PeerConnected
    from tpunode.store import LogKV
    from tpunode.wire import MsgTx, encode_message

    send_tail = asyncio.Event()
    writers: list = []
    server = await remote_server(
        net, blocks, [encode_message(net, MsgTx(tx)) for tx in tail],
        send_tail, writers,
    )
    port = server.sockets[0].getsockname()[1]
    pub = Publisher(name="chip-smoke", maxsize=None)  # exact counts
    cfg = NodeConfig(
        net=net,
        store=LogKV(os.path.join(store_dir, "node.log"), fsync=True),
        pub=pub,
        peers=[f"127.0.0.1:{port}"],
        discover=False,
        verify=run.verify_cfg(),
        prevout_lookup=synth_prevout,
        utxo=True,
        # config 3's planner settings; no get_blocks, no pushes
        ibd=IbdConfig(batch_blocks=24, tick_interval=0.02),
    )
    chain_txs = len(blocks) * (TXS_PER_BLOCK + 1)  # + a coinbase per block
    seen: collections.Counter = collections.Counter()
    verdicts: dict = {}
    shed = 0
    progress = asyncio.Event()

    async def consume(events):
        nonlocal shed
        while True:
            ev = await events.receive()
            if isinstance(ev, TxVerdict):
                seen[ev.txid] += 1
                verdicts[ev.txid] = ev
                progress.set()
            elif isinstance(ev, VerifyShed):
                shed += ev.dropped_txs

    async def until(cond, seconds: float, what: str):
        deadline = time.monotonic() + seconds
        while not cond():
            check(time.monotonic() < deadline,
                  f"timed out after {seconds:.0f}s waiting for {what} "
                  f"({len(seen)} verdicts)")
            progress.clear()
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(progress.wait(), 0.05)

    node = Node(cfg)
    # the engine warms up from construction; with the device up before
    # the first peer connects, no block waits on a compile
    warmup_s = await await_warmup(node.verify_engine, run)
    out: dict = {"warmup_seconds": warmup_s}
    try:
        async with pub.subscription() as events:
            async with node:
                t0 = time.monotonic()
                await asyncio.wait_for(events.receive_match(
                    lambda ev: ev if isinstance(ev, PeerConnected) else None
                ), 30)
                await asyncio.wait_for(events.receive_match(
                    lambda ev: ev if isinstance(ev, ChainSynced) else None
                ), 120)
                check(node.chain.get_best().height == len(blocks),
                      "header sync stopped short")
                out["header_seconds"] = round(time.monotonic() - t0, 2)
                consumer = asyncio.ensure_future(consume(events))
                try:
                    t0 = time.monotonic()
                    await until(lambda: len(seen) >= chain_txs,
                                run.args.node_timeout, "chain verdicts")
                    await until(lambda: node.utxo.height >= len(blocks),
                                60, "utxo catch-up")
                    out["block_seconds"] = round(time.monotonic() - t0, 2)
                    out["utxo_height"] = node.utxo.height
                    out["ibd"] = node.ibd.stats()
                    t0 = time.monotonic()
                    send_tail.set()
                    await until(lambda: len(seen) >= chain_txs + len(tail),
                                120, "mempool verdicts")
                    out["mempool_seconds"] = round(time.monotonic() - t0, 2)
                    await asyncio.sleep(0.5)  # a duplicate verdict would land
                finally:
                    consumer.cancel()
                    with contextlib.suppress(asyncio.CancelledError):
                        await consumer
                out["engine"] = node.verify_engine
    finally:
        for w in writers:  # before wait_closed(): it waits for handlers
            w.close()
        server.close()
        await server.wait_closed()
        cfg.store.close()
    out.update(seen=seen, verdicts=verdicts, shed=shed)
    return out


def leg_node(run: Run, cache_dir: str, segments, tail) -> None:
    import jax

    from benchmarks.txgen import assemble_chain
    from tpunode.events import events
    from tpunode.params import BCH_REGTEST

    txs = [tx for seg, _ in segments for tx in seg]
    ref = collections.Counter()
    for _, tot in segments:
        ref.update(tot)
    blocks = assemble_chain(BCH_REGTEST, txs, TXS_PER_BLOCK)
    check(len(blocks) == run.args.blocks, "chain assembly lost blocks")
    tail_want, tail_items = tail_expectations(tail)
    check(0 < sum(v for v, _ in tail_want.values()) < len(tail),
          "degenerate mempool tail")
    note(f"chain: {len(blocks)} blocks, {len(txs)} txs, {ref['sigs']} sigs, "
         f"{ref['items']} device items; tail {len(tail)} txs")

    # the second pass over the same shapes: a fresh engine in a process
    # whose in-memory executables are gone finds them in the persistent
    # cache (hits, no new entries, first-call seconds collapse)
    jax.clear_caches()
    hits0, entries0 = run.cache["cache_hits"], cache_entries(cache_dir)
    seq0, before, occ0 = events.seq(), counters(), occupancy()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as store_dir:
        got = asyncio.run(run_node(run, blocks, tail, store_dir))

    seen, verdicts = got["seen"], got["verdicts"]
    chain_ids = {tx.txid for b in blocks for tx in b.txs}
    check(set(seen) == chain_ids | set(tail_want),
          "TxVerdict txids differ from the txs served")
    dupes = [t.hex() for t, n in seen.items() if n != 1]
    check(not dupes, f"{len(dupes)} txs got more than one TxVerdict")
    errors = [v.error for v in verdicts.values() if v.error]
    check(not errors, f"{len(errors)} TxVerdicts carry an error: {errors[:3]}")
    bad = [t.hex() for t in chain_ids if not verdicts[t].valid]
    check(not bad, f"{len(bad)} chain txs judged invalid")
    for txid, (valid, per_sig) in tail_want.items():
        v = verdicts[txid]
        check((v.valid, tuple(v.verdicts)) == (valid, per_sig),
              f"mempool tx {txid.hex()}: got {v.valid} {v.verdicts}, "
              f"reference {valid} {per_sig}")
    chain_sigs = sum(len(verdicts[t].verdicts) for t in chain_ids)
    check(chain_sigs == ref["sigs"],
          f"chain verdicts cover {chain_sigs} sigs, reference {ref['sigs']}")
    extracted = sum(verdicts[t].stats.extracted for t in chain_ids)
    inputs = sum(
        verdicts[t].stats.total_inputs - verdicts[t].stats.coinbase
        for t in chain_ids
    )
    check(inputs == ref["inputs"] and extracted == ref["extracted"],
          f"extraction differs from the reference: {extracted}/{inputs} vs "
          f"{ref['extracted']}/{ref['inputs']}")
    coverage = extracted / inputs
    check(coverage >= 0.90, f"coverage {coverage:.4f} < 0.90")
    check(got["utxo_height"] == len(blocks),
          f"utxo height {got['utxo_height']} != {len(blocks)}")
    check(got["ibd"]["refetches"] == 0, f"ibd refetched: {got['ibd']}")
    check(got["shed"] == 0, f"VerifyShed dropped {got['shed']} txs")

    submitted = ref["items"] + tail_items
    served = check_served(run, got["engine"], before, submitted)
    fields = dict(
        blocks=len(blocks), txs=len(chain_ids), sigs=chain_sigs,
        device_items=submitted, coverage=round(coverage, 4),
        mempool_txs=len(tail),
        mempool_invalid=sum(not v for v, _ in tail_want.values()),
        utxo_height=got["utxo_height"],
        refetches=got["ibd"]["refetches"],
        fetched_blocks=got["ibd"].get("fetched_blocks"),
        warmup_seconds=got["warmup_seconds"],
        header_seconds=got["header_seconds"],
        block_seconds=got["block_seconds"],
        mempool_seconds=got["mempool_seconds"],
        first_call_seconds=compile_events(seq0),
        occupancy=occupancy_since(occ0), served=served,
        cache_hits=run.cache["cache_hits"] - hits0,
        cache_entries_before=entries0,
        cache_entries_after=cache_entries(cache_dir),
    )
    if run.args.blocks != 1000:
        fields["reduced"] = {"blocks": run.args.blocks, "of": 1000}
    if not run.dryrun:
        fields["programs"] = check_programs()
        check(fields["cache_hits"] >= 4,
              f"warm pass: {fields['cache_hits']} persistent-cache hits")
        check(fields["cache_entries_after"] == entries0,
              "warm pass wrote new cache entries: a program was not found")
    run.emit("node", **fields)


# ---- leg: four chips -------------------------------------------------------


def leg_four_chips(run: Run, items: list, one_chip: list) -> None:
    import jax

    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if len(tpus) < 4 and not run.dryrun:
        run.emit("four_chips", ran=False, visible=len(tpus))
        return
    out: dict = {"ran": not run.dryrun, "visible": len(tpus)}
    if not run.dryrun:
        out["sharded"] = four_chips_sharded(run, items, one_chip)
    out["fleet"] = four_chips_fleet(run, items, one_chip)
    run.emit("four_chips", **out)


def four_chips_sharded(run: Run, items: list, one_chip: list) -> dict:
    """One lane shard_mapped over four chips (8192 per shard)."""
    from tpunode.verify import multichip
    from tpunode.verify.kernel import collect_verdicts
    from tpunode.verify.raw import pack_items

    before, t0 = counters(), time.monotonic()
    eng, got, _ = asyncio.run(
        run_engine(run, run.verify_cfg(mesh_devices=4), items)
    )
    check(got == one_chip, "sharded verdicts differ from the one-chip leg's")
    served = check_served(run, eng, before, len(items))
    ok, count = multichip.dispatch_raw_sharded(
        pack_items(items[:32768]), eng._mesh(), pad_to=32768
    )
    ids = sorted(d.id for d in ok.sharding.device_set)
    check(len(ids) == 4, f"sharded output lives on devices {ids}")
    check(collect_verdicts(ok, count) == one_chip[:32768],
          "direct sharded dispatch differs from the one-chip leg's")
    check_programs()
    return {"device_ids": ids, "served": served,
            "seconds": round(time.monotonic() - t0, 2)}


def four_chips_fleet(run: Run, items: list, one_chip: list) -> dict:
    """Four one-chip fleet hosts under the work-stealing dispatcher (in
    --dryrun: four cpu-rung hosts, which debugs the counters only)."""
    mesh = {} if run.dryrun else {"mesh_devices": 4}
    before, t0 = counters(), time.monotonic()
    eng, got, _ = asyncio.run(
        run_engine(run, run.verify_cfg(mesh_hosts=4, **mesh), items + items)
    )
    check(got == one_chip + one_chip,
          "fleet verdicts differ from the one-chip leg's")
    served = check_served(run, eng, before, 2 * len(items))
    by_host = eng.ledger().get("by_host", {})
    check(set(by_host) == set(eng._hosts)
          and all(s > 0 for s in by_host.values()),
          f"not every fleet host served lanes: {by_host}")
    out = {"served": served, "busy_seconds_by_host": by_host,
           "steals": eng.stats()["fleet"]["steals"],
           "seconds": round(time.monotonic() - t0, 2)}
    if not run.dryrun:
        host_dev = {
            name: [d.id for d in hs.mesh.devices.flat]
            if hs.mesh is not None else None
            for name, hs in eng._hosts.items()
        }
        check(all(v is not None and len(v) == 1 for v in host_dev.values()),
              f"fleet hosts without a one-chip sub-mesh: {host_dev}")
        check(len({v[0] for v in host_dev.values()}) == 4,
              f"fleet hosts share a device: {host_dev}")
        check_programs()
        out["host_device_ids"] = host_dev
    return out


# ---- main ------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dryrun", action="store_true",
                    help="same legs, tiny sizes, backend='cpu'; never ok")
    ap.add_argument("--blocks", type=int, default=None,
                    help="chain length (default 1000; --dryrun 8); a cut "
                    "is printed as `reduced`")
    ap.add_argument("--lanes", type=int, default=4,
                    help="full device_batch lanes in the engine leg")
    ap.add_argument("--tail", type=int, default=None,
                    help="extra items after the full lanes "
                    "(default 1000; --dryrun 37)")
    ap.add_argument("--mempool-txs", type=int, default=48)
    ap.add_argument("--workers", type=int, default=0,
                    help="chain-generation processes (0 = cores - 3)")
    ap.add_argument("--node-timeout", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=0xC41B)
    args = ap.parse_args(argv)
    if args.blocks is None:
        args.blocks = 8 if args.dryrun else 1000
    if args.tail is None:
        args.tail = 37 if args.dryrun else 1000
    run = Run(args)

    import jax

    dev = jax.devices()[0]
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    run.jax_version = jax.__version__
    if dev.platform != "tpu" and not args.dryrun:
        print(f"chip_smoke: JAX reports {run.device}, not a TPU — nothing "
              "was run (use --dryrun for the CPU harness check)",
              file=sys.stderr)
        return 1
    note(f"devices: {run.device} jax {run.jax_version}")

    cache_dir = leg_env(run)
    workers = args.workers or max(1, (os.cpu_count() or 4) - 3)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        # the chain is signed while the engine leg compiles and runs
        chain_job = start_chain_jobs(run, pool)
        items, one_chip = leg_engine(run, cache_dir)
        note("engine leg done; waiting for the chain")
        segments = chain_job.get(timeout=900)
    tail, _totals = segments.pop()
    leg_node(run, cache_dir, segments, tail)
    leg_four_chips(run, items, one_chip)

    run.emit(
        "summary",
        seconds=round(time.monotonic() - T0, 1),
        sigs={"engine": run.legs["engine"]["sigs"],
              "node": run.legs["node"]["device_items"]},
        legs=list(run.legs),
        four_chips=run.legs["four_chips"].get("ran"),
    )
    # the last line: exactly these two keys, the device as JAX reports it
    print(json.dumps({"ok": not args.dryrun, "device": run.device}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
