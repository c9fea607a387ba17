"""Scenario workers: one device step-time check and the device-free
scenario harnesses, each run in this process and printing ONE JSON line.

    python bench.py                 # device worker: fails without a TPU
    python bench.py --<scenario>    # mempool | chaos | recovery | pipeline |
                                    # ibd | mesh | mesh-e2e | mesh-device |
                                    # serve | observability

The device worker (``_worker_bench``) compiles the Pallas verify kernel at
one batch shape on the chip, cross-checks the C++ verifier and times a few
steps; every result names the device it ran on, and a process that finds
no TPU fails instead of timing a CPU.  The ``--<scenario>`` workers drive
node subsystems on the C++ / oracle rungs (the verify skill and the slow
test tier run them); their timings are host-clock numbers about those
rungs, never device metrics.

This file is what is left of the old multi-process driver; ROADMAP S1
replaces it with one cell table and one runner.  ``chip_smoke.py`` is the
proof that the main path runs on the chip.

Run from the repo root.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

BATCH = int(os.environ.get("TPUNODE_BENCH_BATCH", 32768))
UNIQUE = 512
TIMED_ITERS = int(os.environ.get("TPUNODE_BENCH_ITERS", 5))


def _progress(msg: str) -> None:
    print(f"[bench-worker] {msg}", file=sys.stderr, flush=True)

def _sanitizer_counts(event_counts: dict, metrics) -> dict:
    """asyncsan/threadsan/watchdog regression signals for the BENCH JSON
    (ISSUE 3 + 18 satellites): leaked supervised tasks, watchdog stall
    episodes, and the lock sanitizer's cycle/reentry/hold watermarks seen
    by this process.  A nonzero trajectory across rounds flags a
    concurrency regression the throughput number alone would hide.  The
    threadsan keys are registry counters (not event counts) so they are
    meaningful whether or not TPUNODE_THREADSAN armed this run — zeros
    when off."""
    from tpunode.threadsan import registry as _ts

    return {
        "task_leak": int(event_counts.get("asyncsan.task_leak", 0)),
        "watchdog_stall": int(event_counts.get("watchdog.stall", 0)),
        "task_leaks_metric": metrics.get("asyncsan.task_leaks"),
        "lock_cycles": int(_ts.lock_cycles),
        "lock_reentries": int(_ts.lock_reentries),
        "max_hold_ms": round(_ts.max_hold_seconds * 1000.0, 3),
    }


def _worker_bench() -> None:
    """Device step-time check, in this process: the Pallas verify kernel at
    ``TPUNODE_BENCH_BATCH`` (default 32768) on the chip.

    Prints one JSON line: {"ok": true, rate, device, kernel, step_ms,
    compile_s, init_s, ...}, or {"ok": false, "error": ...} and exit code 1
    — with no TPU, on a compile error, or (+"fatal") on a verdict mismatch.
    """
    batch = int(os.environ.get("TPUNODE_BENCH_BATCH", BATCH))
    iters = int(os.environ.get("TPUNODE_BENCH_ITERS", TIMED_ITERS))
    try:
        import jax

        from tpunode.verify.engine import enable_compile_cache

        enable_compile_cache()

        t0 = time.perf_counter()
        dev = jax.devices()[0]
        init_s = time.perf_counter() - t0
        _progress(f"backend up: {dev} in {init_s:.1f}s")
        if dev.platform != "tpu":
            print(json.dumps(
                {"ok": False,
                 "error": f"platform is {dev.platform!r}, not tpu"}
            ))
            sys.exit(1)

        from benchmarks.common import device_kind, make_triples, tile
        from tpunode.verify.cpu_native import load_native_verifier
        from tpunode.verify.kernel import collect_verdicts, prepare_batch
        from tpunode.verify.pallas_kernel import verify_blocked

        base = make_triples(min(UNIQUE, batch))
        items = tile(base, batch)
        prep = prepare_batch(items, pad_to=batch)
        buf = jax.device_put(prep.buf, dev)
        # ECDSA-only workload: the variant with the acceptance pows pruned
        # at trace time is the program the engine dispatches for it
        kw = {"schnorr_free": prep.schnorr_free}
        _progress(f"host prep done, compiling pallas at batch {batch}...")
        t0 = time.perf_counter()
        out = verify_blocked(buf, **kw)  # compile + first run
        # ONE bulk transfer (collect_verdicts): iterating the device array
        # would issue one device round-trip PER ELEMENT
        got = collect_verdicts(out, len(base))
        compile_s = time.perf_counter() - t0
        _progress(f"compiled+ran in {compile_s:.1f}s, checking the C++ verifier...")
        if got != load_native_verifier().verify_batch(base):
            print(json.dumps(
                {"ok": False, "fatal": True,
                 "error": "device/oracle verdict mismatch"}
            ))
            sys.exit(1)

        from tpunode.events import events as _events
        from tpunode.metrics import metrics
        from tpunode.trace import profile_to, span
        from tpunode.tracectx import start_trace, tracer
        from tpunode.verify.engine import VerifyEngine

        # Device-profile capture (ISSUE 16): TPUNODE_PROFILE captures into
        # that directory; with TPUNODE_PROFILE_DIR set instead, each run
        # captures into its own labeled subdirectory and the path rides
        # along in the JSON.
        prof_dir = os.environ.get("TPUNODE_PROFILE")
        profile_path = None
        if not prof_dir:
            prof_base = os.environ.get("TPUNODE_PROFILE_DIR")
            if prof_base:
                profile_path = os.path.join(
                    prof_base, f"bench-pallas-b{batch}-{int(time.time())}"
                )
                prof_dir = profile_path
        times = []
        with profile_to(prof_dir):
            for _ in range(iters):
                # each timed step is one causal trace: the slowest land in
                # the slowest_traces section, so a straggler step is
                # attributable (device vs readback) after the fact
                with start_trace("bench.step", batch=batch):
                    t0 = time.perf_counter()
                    # spanned like the engine's dispatch so the telemetry
                    # section reports the same distribution the node would
                    with span("verify.dispatch"):
                        verify_blocked(buf, **kw).block_until_ready()
                    times.append(time.perf_counter() - t0)
                metrics.observe(
                    "verify.occupancy",
                    1.0,  # the bench pads with real (tiled) items
                    buckets=VerifyEngine.OCCUPANCY_BUCKETS,
                )
        if profile_path is not None and not os.path.isdir(profile_path):
            profile_path = None  # profiler unavailable: nothing captured
        dt = statistics.median(times)
        print(
            json.dumps(
                {
                    "ok": True,
                    "rate": batch / dt,
                    "profile_path": profile_path,
                    "device": device_kind(),
                    "devices": len(jax.devices()),
                    "jax": jax.__version__,
                    "kernel": "pallas",
                    "batch": batch,
                    "step_ms": round(dt * 1e3, 3),
                    "compile_s": round(compile_s, 1),
                    "init_s": round(init_s, 1),
                    "telemetry": metrics.telemetry(),
                    "slowest_traces": tracer.slowest(3),
                    "sanitizers": _sanitizer_counts(
                        _events.counts(), metrics
                    ),
                }
            )
        )
    except Exception as e:  # noqa: BLE001 — one JSON line, then the exit code
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))
        sys.exit(1)


def _worker_mempool() -> None:
    """Duplicate-heavy mempool-ingest scenario (ISSUE 5 satellite).

    A full Node with the mempool subsystem and the ORACLE verify backend
    (device-free) ingests
    heavily-overlapping tx sets from 4 in-process wire-speaking peers —
    one announcer serving ``getdata`` plus three firehose pushers all
    relaying the SAME unique set, with a few parent/child pairs pushed
    child-first to exercise orphan resolution.  Reports ingest
    efficiency: dedup hit-rate (the batch slots NOT wasted on
    re-verifying known txs), admission latency p50/p99 from the
    ``span.mempool.admit`` histogram, and orphan resolutions.  Prints one
    JSON line.
    """
    import asyncio

    n_txs = int(os.environ.get("TPUNODE_BENCH_MEMPOOL_TXS", 96))
    n_pairs = 4
    n_pushers = 3
    try:
        from benchmarks.txgen import gen_signed_txs
        from tests.fakenet import TxRelay, dummy_peer_connect
        from tests.fixtures import all_blocks
        from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
        from tpunode.mempool import MempoolConfig
        from tpunode.metrics import metrics
        from tpunode.store import MemoryKV
        from tpunode.verify.engine import VerifyConfig

        net = BCH_REGTEST
        _progress(f"generating {n_txs} txs + {n_pairs} orphan pairs...")
        shared = gen_signed_txs(n_txs, inputs_per_tx=1, seed=0x3E3)
        pairs = [
            gen_signed_txs(2, inputs_per_tx=1, seed=0x0A20 + i,
                           segwit_every=2)
            for i in range(n_pairs)
        ]
        # child before parent: each pair parks then resolves
        orphan_feed = [t for funding, spender in pairs
                       for t in (spender, funding)]
        unique = {t.txid for t in shared} | {t.txid for t in orphan_feed}
        blocks = all_blocks()
        relays = {
            # one announcer: inv -> want-list -> getdata -> serve
            18801: TxRelay(shared, announce=True, mode="serve"),
            # orphan pusher: children first, then their parents
            18805: TxRelay(announce=False, push=orphan_feed),
        }
        for i in range(n_pushers):  # full-overlap firehose pushers
            relays[18802 + i] = TxRelay(announce=False, push=shared)

        async def run() -> dict:
            pub = Publisher(name="bench-mempool", maxsize=None)
            cfg = NodeConfig(
                net=net,
                store=MemoryKV(),
                pub=pub,
                peers=[f"[::1]:{port}" for port in relays],
                discover=False,
                max_peers=len(relays),
                connect=lambda sa: dummy_peer_connect(
                    net, blocks, relay=relays.get(sa[1])
                ),
                verify=VerifyConfig(backend="oracle", max_wait=0.0),
                mempool=MempoolConfig(tick_interval=0.05),
            )
            before = {
                name: metrics.get(name)
                for name in (
                    "mempool.admitted", "mempool.dedup_hits",
                    "mempool.announcements", "mempool.fetched",
                    "mempool.orphan_resolved", "mempool.orphaned",
                )
            }
            verdicts: set = set()
            t0 = time.perf_counter()
            timed_out = False
            async with pub.subscription() as events:
                async with Node(cfg):
                    while unique - verdicts:
                        try:
                            ev = await asyncio.wait_for(
                                events.receive(), 30.0
                            )
                        except asyncio.TimeoutError:
                            timed_out = True
                            break
                        if isinstance(ev, TxVerdict):
                            verdicts.add(ev.txid)
                    dt = time.perf_counter() - t0
                    # the last verdict can land while duplicate pushes
                    # are still queued: drain to the known delivery
                    # floor (every pusher relays the full shared set),
                    # then to quiescence — the serve-mode announcer's
                    # txs re-arrive via the push path too, an extra the
                    # floor can't predict — so the dedup numbers are
                    # not racily undercounted
                    floor = n_pushers * len(shared) + len(orphan_feed)

                    def _deliveries() -> float:
                        return (
                            metrics.get("mempool.admitted")
                            - before["mempool.admitted"]
                            + metrics.get("mempool.dedup_hits")
                            - before["mempool.dedup_hits"]
                        )

                    drain_deadline = time.perf_counter() + 20.0
                    last = -1.0
                    while time.perf_counter() < drain_deadline:
                        cur = _deliveries()
                        if cur >= floor and cur == last:
                            break  # floor reached and no growth for 0.2s
                        last = cur
                        await asyncio.sleep(0.2)
                    d = {
                        name: metrics.get(name) - v0
                        for name, v0 in before.items()
                    }
            hist = metrics.histogram("span.mempool.admit")
            deliveries = d["mempool.admitted"] + d["mempool.dedup_hits"]
            out = {
                "ok": not timed_out,
                "unique_txs": len(unique),
                "verdicts": len(verdicts),
                "deliveries": int(deliveries),
                "dedup_hits": int(d["mempool.dedup_hits"]),
                "dedup_hit_rate": round(
                    d["mempool.dedup_hits"] / deliveries, 4
                ) if deliveries else 0.0,
                "announcements": int(d["mempool.announcements"]),
                "fetched": int(d["mempool.fetched"]),
                "orphans_parked": int(d["mempool.orphaned"]),
                "orphan_resolutions": int(d["mempool.orphan_resolved"]),
                "admission_p50_ms": round(hist.quantile(0.5) * 1e3, 3)
                if hist is not None and hist.count else None,
                "admission_p99_ms": round(hist.quantile(0.99) * 1e3, 3)
                if hist is not None and hist.count else None,
                "wall_s": round(dt, 2),
                "txs_per_s": round(len(verdicts) / dt, 1) if dt else 0.0,
            }
            if timed_out:
                out["error"] = (
                    f"timed out with {len(unique - verdicts)} verdicts "
                    "outstanding"
                )
            return out

        _progress("running mempool fan-in scenario...")
        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_chaos() -> None:
    """Chaos resilience scenario (ISSUE 7): a full Node + mempool under a
    seeded fault plan — peer garbage on one pusher, random session drops,
    mempool-mailbox delivery delay, and a mid-run device loss — with the
    device SIMULATED (instant warmup + host-computed verdicts on the
    genuine tpu dispatch rung), so the breaker/ladder machinery is
    exercised without a device.  Reports verdict conservation (every
    unique tx exactly one verdict, none with an error, no stuck
    PENDING), failover and breaker-transition counts, recovery latency
    p50/p99, and the sanitizer signals.  Prints one JSON line."""
    import asyncio

    n_txs = int(os.environ.get("TPUNODE_BENCH_CHAOS_TXS", 48))
    seed = int(os.environ.get("TPUNODE_BENCH_CHAOS_SEED", 1337))
    try:
        from benchmarks.txgen import gen_signed_txs
        from tests.fakenet import TxRelay, dummy_peer_connect
        from tests.fixtures import all_blocks
        from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
        from tpunode.actors import task_registry
        from tpunode.chaos import ChaosPlan, chaos
        from tpunode.events import events as _events
        from tpunode.mempool import MempoolConfig
        from tpunode.metrics import metrics
        from tpunode.store import MemoryKV
        from tpunode.verify.engine import VerifyConfig, VerifyEngine

        # Simulated device: the engine's real tpu rung runs, verdicts are
        # computed on the host — breaker engaged, verify.tpu_items counted.
        import tpunode.verify.kernel as K
        from tpunode.verify.ecdsa_cpu import verify_batch_cpu

        VerifyEngine._warmup_fn = staticmethod(
            lambda bs, db=0: "tpu:chaos-sim"
        )
        K.dispatch_batch_tpu_raw = lambda chunk, pad_to=None: (
            verify_batch_cpu(chunk.to_tuples()), len(chunk),
        )
        K.collect_verdicts = lambda arr, count: arr

        plan_spec = os.environ.get("TPUNODE_CHAOS") or (
            f"seed={seed};"
            "peer.recv:garbage:p=0.05,n=2,match=18903;"
            "peer.recv:drop:p=0.02,n=3;"
            "mailbox.send:delay:p=0.05,dur=0.005,match=mempool;"
            "engine.dispatch:device_loss:match=tpu,after=1,n=3"
        )
        chaos.install(ChaosPlan.parse(plan_spec))
        net = BCH_REGTEST
        _progress(f"generating {n_txs} txs for the chaos scenario...")
        txs = gen_signed_txs(n_txs, inputs_per_tx=1, seed=0xC7A05)
        unique = {t.txid for t in txs}
        blocks = all_blocks()
        relays = {
            18901: TxRelay(txs, announce=True, mode="serve"),
            18902: TxRelay(txs, announce=True, mode="serve"),
            18903: TxRelay(announce=False, push=txs),  # the garbage target
        }

        def probe_items(count: int):
            """Tiny known-answer batch for driving the breaker recovery."""
            from tpunode.verify.ecdsa_cpu import (
                CURVE_N, GENERATOR, point_mul, sign,
            )

            items, expected = [], []
            for i in range(count):
                priv = (0xBEEF + i) % CURVE_N or 1
                pub_pt = point_mul(priv, GENERATOR)
                z = (0xF00D << i) % CURVE_N
                r, s = sign(priv, z, 0xC0FFEE + i)
                if i % 2:
                    z ^= 1
                items.append((pub_pt, z, r, s))
                expected.append(i % 2 == 0)
            return items, expected

        async def run() -> dict:
            pub = Publisher(name="bench-chaos", maxsize=None)
            cfg = NodeConfig(
                net=net,
                store=MemoryKV(),
                pub=pub,
                peers=[f"[::1]:{port}" for port in relays],
                discover=False,
                max_peers=len(relays),
                connect=lambda sa: dummy_peer_connect(
                    net, blocks, relay=relays.get(sa[1])
                ),
                verify=VerifyConfig(
                    backend="auto", max_wait=0.005, batch_size=64,
                    min_tpu_batch=1, breaker_threshold=2,
                    breaker_cooldown=0.2,
                ),
                mempool=MempoolConfig(tick_interval=0.05),
            )
            failovers0 = metrics.get("verify.failovers")
            stalls0 = _events.counts().get("watchdog.stall", 0)
            verdict_counts: dict = {}
            errors = 0
            t0 = time.perf_counter()
            timed_out = False
            async with pub.subscription() as sub:
                async with Node(cfg) as node:
                    eng = node.verify_engine
                    deadline = time.monotonic() + 60.0
                    while (
                        unique - set(verdict_counts)
                        and time.monotonic() < deadline
                    ):
                        try:
                            ev = await asyncio.wait_for(sub.receive(), 5.0)
                        except asyncio.TimeoutError:
                            continue
                        if isinstance(ev, TxVerdict):
                            verdict_counts[ev.txid] = (
                                verdict_counts.get(ev.txid, 0) + 1
                            )
                            if ev.error is not None:
                                errors += 1
                    if unique - set(verdict_counts):
                        timed_out = True
                    # drive the remaining injected device losses + the
                    # half-open canary recovery with direct batches
                    items, expected = probe_items(4)
                    drive_deadline = time.monotonic() + 30.0
                    conserved_probe = True
                    while time.monotonic() < drive_deadline:
                        got = await eng.verify(items)
                        if got != expected:
                            conserved_probe = False
                            break
                        if (
                            eng.breaker.opens >= 1
                            and eng.breaker.state == "ready"
                        ):
                            break
                        await asyncio.sleep(0.02)
                    tpu0 = metrics.get("verify.tpu_items")
                    await eng.verify(items)
                    device_restored = (
                        eng.breaker.state == "ready"
                        and metrics.get("verify.tpu_items") > tpu0
                    )
                    # stuck PENDING sweep (mempool processes our observed
                    # verdicts asynchronously: poll briefly)
                    stuck = 0
                    sweep_deadline = time.monotonic() + 10.0
                    while time.monotonic() < sweep_deadline:
                        stuck = sum(
                            1
                            for t in unique
                            if node.mempool.state(t) == "pending"
                        )
                        if not stuck:
                            break
                        await asyncio.sleep(0.1)
                    breaker = dict(eng.breaker.stats())
                    wall = time.perf_counter() - t0
            leaks = task_registry.report_leaks()
            dupes = sum(1 for v in verdict_counts.values() if v != 1)
            rec = metrics.histogram("verify.breaker_recovery_seconds")
            conserved = (
                not timed_out
                and dupes == 0
                and errors == 0
                and stuck == 0
                and conserved_probe
            )
            out = {
                "ok": conserved and device_restored,
                "plan": plan_spec,
                "unique_txs": len(unique),
                "verdicts": sum(verdict_counts.values()),
                "duplicate_verdicts": dupes,
                "error_verdicts": errors,
                "stuck_pending": stuck,
                "verdict_conservation": conserved,
                "failovers": int(
                    metrics.get("verify.failovers") - failovers0
                ),
                "breaker_opens": breaker["opens"],
                "breaker_closes": breaker["closes"],
                "breaker_state": breaker["state"],
                "device_path_restored": device_restored,
                "recovery_p50_ms": round(rec.quantile(0.5) * 1e3, 3)
                if rec is not None and rec.count else None,
                "recovery_p99_ms": round(rec.quantile(0.99) * 1e3, 3)
                if rec is not None and rec.count else None,
                "injections": {
                    f["fault"]: f["fired"]
                    for f in chaos.stats()["faults"]
                },
                "task_leaks": len(leaks),
                "watchdog_stalls": int(
                    _events.counts().get("watchdog.stall", 0) - stalls0
                ),
                "wall_s": round(wall, 2),
            }
            if timed_out:
                out["error"] = (
                    f"timed out with "
                    f"{len(unique - set(verdict_counts))} verdicts "
                    "outstanding"
                )
            return out

        _progress("running chaos resilience scenario...")
        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_recovery() -> None:
    """Crash-recovery scenario worker (ISSUE 9): makes recovery cost a
    tracked number.  Measures (1) reopen/replay latency at two log sizes
    (records/s and MB/s of the streamed v2 replay), (2) the compaction
    pause on the larger store, and (3) a bounded kill-torture sweep —
    real writer children killed at seeded append/rotate/compact points +
    bit-flip detection runs, reporting the pass rate.  jax is never
    imported."""
    import shutil
    import tempfile

    torture_budget = float(
        os.environ.get("TPUNODE_BENCH_RECOVERY_TORTURE_S", 75)
    )
    try:
        from tpunode.store import LogKV, put_op
        from tpunode.torture import sweep

        out: dict = {"ok": True, "replay": []}
        base = tempfile.mkdtemp(prefix="tpunode-recovery-")
        try:
            # 1) reopen/replay latency vs log size
            for label, n_records in (("small", 2_000), ("large", 20_000)):
                _progress(f"building {label} log ({n_records} records)...")
                path = os.path.join(base, f"replay-{label}", "kv.log")
                s = LogKV(path)
                batch = [
                    put_op(b"k%08d" % i, (b"v%08d" % i) * 12)
                    for i in range(n_records)
                ]
                for i in range(0, n_records, 500):
                    s.write_batch(batch[i : i + 500])
                s.close()
                size = sum(
                    os.path.getsize(os.path.join(d, f))
                    for d, _, fs in os.walk(os.path.dirname(path))
                    for f in fs
                )
                t0 = time.perf_counter()
                s2 = LogKV(path)
                open_s = time.perf_counter() - t0
                row = {
                    "label": label,
                    "records": n_records,
                    "bytes": size,
                    "open_ms": round(open_s * 1e3, 1),
                    "records_per_s": round(n_records / open_s),
                    "mb_per_s": round(size / open_s / 1e6, 1),
                }
                # 2) compaction pause on the large store (overwrites first
                # so compaction has real garbage to drop)
                if label == "large":
                    for i in range(0, 5_000, 500):
                        s2.write_batch(
                            [put_op(b"k%08d" % j, b"fresh" * 16)
                             for j in range(i, i + 500)]
                        )
                    t0 = time.perf_counter()
                    s2.compact()
                    out["compaction_pause_ms"] = round(
                        (time.perf_counter() - t0) * 1e3, 1
                    )
                s2.close()
                out["replay"].append(row)
            # 3) bounded kill-torture sweep (real subprocess children)
            _progress("running kill-torture sweep...")
            res = sweep(
                os.path.join(base, "torture"), seeds=(1,), ops=24,
                seg_bytes=1000, compact_every=10, bit_flips=2,
                budget_s=torture_budget,
            )
            out["torture"] = {
                "kill_points": res.points,
                "completed_runs": res.completed,
                "corruption_detected": res.corruption_detected,
                "violations": res.violations[:10],
                "pass": res.ok,
            }
            if not res.ok:
                out["ok"] = False
                out["error"] = (
                    f"{len(res.violations)} torture invariant violation(s)"
                )
        finally:
            shutil.rmtree(base, ignore_errors=True)
        print(json.dumps(out))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_pipeline() -> None:
    """Streaming-pipeline A/B (ISSUE 10): e2e ingest throughput of the
    duplicate-heavy mempool firehose through a full Node on the cpu
    proxy, SERIAL (``pipeline_depth=1, extract_workers=1`` — the
    pre-pipeline dispatch) vs PIPELINED (depth 2, pooled extraction).

    The workload is signature-bound by construction (2-input signed txs,
    every tx pushed twice so the mempool's dedup admission sees the
    duplicate-heavy shape): the A/B isolates what the lane packer +
    overlapped dispatch + parallel extraction buy on identical traffic.
    Reports e2e sigs/s both ways, the speedup, mean lane occupancy
    (pack efficiency) under saturation, host stage busy fractions
    (extract/dispatch/commit span time over wall), and an
    extraction-only worker scaling curve 1→4.  Prints one JSON line.
    """
    import asyncio

    n_txs = int(os.environ.get("TPUNODE_BENCH_PIPELINE_TXS", 2500))
    try:
        from benchmarks.txgen import gen_signed_txs
        from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, TxVerdict
        from tpunode.mempool import MempoolConfig
        from tpunode.metrics import metrics
        from tpunode.peer import PeerMessage
        from tpunode.store import MemoryKV
        from tpunode.verify.engine import VerifyConfig
        from tpunode.wire import LazyTx, MsgTx

        from tpunode import txextract

        if not txextract.have_native_extract():
            print(json.dumps(
                {"ok": False, "error": "native extractor unavailable"}
            ))
            return
        net = BCH_REGTEST
        _progress(f"generating {n_txs} signed txs (2 inputs each)...")
        signed = gen_signed_txs(n_txs, inputs_per_tx=2, seed=0x919E)
        # wire form (LazyTx with raw bytes, exactly what MsgTx decodes
        # to): the accumulator/native-extract fast path requires raw
        txs = [LazyTx(t.serialize()) for t in signed]
        n_sigs = sum(len(t.inputs) for t in signed)
        unique = {t.txid for t in signed}

        class _Pusher:  # minimal peer surface for the router/mempool
            def __init__(self, label):
                self.label = label

            def kill(self, exc):  # pragma: no cover - healthy traffic
                pass

        async def run_once(depth: int, workers: int) -> dict:
            metrics.reset()
            pub = Publisher(name="bench-pipeline", maxsize=None)
            cfg = NodeConfig(
                net=net,
                store=MemoryKV(),
                pub=pub,
                peers=[],  # traffic is injected directly on the router
                discover=False,
                verify=VerifyConfig(
                    backend="cpu", max_wait=0.005, batch_size=256,
                    pipeline_depth=depth,
                ),
                mempool=MempoolConfig(tick_interval=0.05),
                extract_workers=workers,
            )
            p1, p2 = _Pusher("fire:1"), _Pusher("fire:2")
            verdicts: set = set()
            timed_out = False
            async with pub.subscription() as events:
                async with Node(cfg) as node:
                    t0 = time.perf_counter()
                    for t in txs:  # firehose + full duplicate push
                        node._peer_pub.publish(
                            PeerMessage(p1, MsgTx(t))
                        )
                        node._peer_pub.publish(
                            PeerMessage(p2, MsgTx(t))
                        )
                    while unique - verdicts:
                        try:
                            ev = await asyncio.wait_for(
                                events.receive(), 30.0
                            )
                        except asyncio.TimeoutError:
                            timed_out = True
                            break
                        if isinstance(ev, TxVerdict):
                            verdicts.add(ev.txid)
                    dt = time.perf_counter() - t0
            out = {
                "pipeline_depth": depth,
                "extract_workers": workers,
                "verdicts": len(verdicts),
                "wall_s": round(dt, 3),
                "sigs_per_s": round(n_sigs / dt, 1) if dt else 0.0,
                "dedup_hits": int(metrics.get("mempool.dedup_hits")),
            }
            pack = metrics.histogram("sched.pack_efficiency")
            if pack is not None and pack.count:
                out["lanes"] = pack.count
                out["pack_efficiency_mean"] = round(pack.mean, 4)
                out["lane_occupancy_p50"] = round(
                    pack.quantile(0.5) or 0.0, 4
                )
            busy = {}
            for stage, name in (
                ("extract", "span.node.extract"),
                ("dispatch", "span.verify.dispatch"),
                ("commit", "span.node.commit"),
            ):
                h = metrics.histogram(name)
                if h is not None and h.count and dt:
                    busy[stage] = round(h.total / dt, 4)
            out["stage_busy"] = busy
            if timed_out:
                out["error"] = (
                    f"timed out with {len(unique - verdicts)} verdicts "
                    "outstanding"
                )
            return out

        def extract_scaling() -> dict:
            """Extraction-only scaling curve: one shard per worker over
            the same tx region, pure native extract (no engine)."""
            from concurrent.futures import ThreadPoolExecutor

            from tpunode.txextract import ParsedTxRegion

            raws = [t.serialize() for t in txs]
            curve: dict = {}
            for w in (1, 2, 4):
                shard_sz = (len(raws) + w - 1) // w
                shards = [
                    (b"".join(raws[i : i + shard_sz]),
                     len(raws[i : i + shard_sz]))
                    for i in range(0, len(raws), shard_sz)
                ]

                def one(shard):
                    data, n = shard
                    with ParsedTxRegion(data, n) as region:
                        return region.extract(intra_amounts=False).count

                best = None
                with ThreadPoolExecutor(max_workers=w) as pool:
                    for _ in range(3):
                        t0 = time.perf_counter()
                        total = sum(pool.map(one, shards))
                        dt = time.perf_counter() - t0
                        assert total > 0
                        best = dt if best is None else min(best, dt)
                curve[str(w)] = round(len(raws) / best, 1)
            return curve

        async def run() -> dict:
            import os as _os

            workers = min(4, _os.cpu_count() or 1)
            _progress("serial baseline (depth 1, 1 extract worker)...")
            serial = await run_once(1, 1)
            _progress(f"pipelined (depth 2, {workers} extract workers)...")
            pipelined = await run_once(2, workers)
            out = {
                "ok": (
                    "error" not in serial and "error" not in pipelined
                ),
                "proxy": "cpu-native",
                "unique_txs": len(unique),
                "sigs": n_sigs,
                "serial": serial,
                "pipelined": pipelined,
            }
            if serial.get("sigs_per_s") and pipelined.get("sigs_per_s"):
                out["speedup"] = round(
                    pipelined["sigs_per_s"] / serial["sigs_per_s"], 3
                )
            _progress("extract-worker scaling curve...")
            out["extract_scaling_txs_per_s"] = extract_scaling()
            for side in ("serial", "pipelined"):
                if "error" in out[side]:
                    out["error"] = f"{side}: {out[side]['error']}"
                    break
            return out

        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_mesh() -> None:
    """Pod-scale fleet-dispatcher scaling (ISSUE 13): e2e verify
    throughput of the cross-host work-stealing fleet at 1/2/4/8-way
    dispatch on the cpu-native proxy.

    Each fleet host runs one dispatch worker whose cpu rung drives the
    C++ engine with ONE OS thread (GIL-released), so k hosts = k cores
    of native verification and the scaling curve prices the DISPATCHER
    itself — lane packing, assignment, stealing, per-host bookkeeping —
    not the device.  1-way is the plain single-host pipeline
    (``mesh_hosts=0, pipeline_depth=1``), the honest serial baseline.
    Acceptance floor (ISSUE 13): >= 0.8x ideal at 4-way.  The worker
    also drives the adversarial campaign pool through the 4-way fleet
    and cross-checks verdict bit-identity against the single-chip path
    (a scheduler that drops, duplicates, or reorders slices would show
    up here, not just in throughput).  Prints one JSON line.
    """
    import asyncio

    sigs = int(os.environ.get("TPUNODE_BENCH_MESH_SIGS", 24576))
    ways_env = os.environ.get("TPUNODE_BENCH_MESH_WAYS_LIST", "1,2,4,8")
    try:
        from benchmarks.campaign import build_pool
        from benchmarks.common import make_triples, tile
        from tpunode.metrics import metrics
        from tpunode.verify.cpu_native import load_native_verifier
        from tpunode.verify.engine import VerifyConfig, VerifyEngine
        from tpunode.verify.raw import pack_items

        if load_native_verifier() is None:
            print(json.dumps(
                {"ok": False, "error": "native verifier unavailable"}
            ))
            return
        ways_list = [int(w) for w in ways_env.split(",") if w.strip()]
        _progress(f"generating {sigs} tiled sigs...")
        uniq = make_triples(min(2048, sigs))
        items = tile(uniq, sigs)
        raw = pack_items(items)
        # Submission grain chosen NOT to divide the 1024-item lane
        # target, so slices genuinely straddle lane boundaries and the
        # curve prices the packer's cross-submission bookkeeping too
        # (review r13: 512 packed two whole submissions per lane).
        sub = 500

        async def run_way(hosts: int) -> dict:
            metrics.reset()
            cfg = VerifyConfig(
                backend="cpu", batch_size=1024, max_wait=0.005,
                pipeline_depth=1, cpu_threads=1, warmup=False,
                mesh_hosts=hosts if hosts >= 2 else 0,
            )
            async with VerifyEngine(cfg) as eng:
                t0 = time.perf_counter()
                futs = [
                    # gathered three lines down; a supervisor would just
                    # add registry churn to the timed window
                    asyncio.ensure_future(  # asyncsan: disable=raw-spawn
                        eng.verify_raw(raw.slice(off, off + sub))
                    )
                    for off in range(0, len(raw), sub)
                ]
                got = await asyncio.gather(*futs)
                dt = time.perf_counter() - t0
                st = eng.stats()
            n = sum(len(g) for g in got)
            assert n == sigs
            out = {
                "hosts": hosts,
                "wall_s": round(dt, 3),
                "sigs_per_s": round(sigs / dt, 1) if dt else 0.0,
            }
            fleet = st.get("fleet")
            if fleet:
                out["steals"] = fleet["steals"]
                out["requeued"] = fleet["requeued"]
            return out

        async def campaign_parity() -> dict:
            import random as _random

            items_c, shapes, expects = build_pool(
                24, _random.Random(0x13E5)
            )
            async def through(hosts: int) -> list:
                cfg = VerifyConfig(
                    backend="cpu", batch_size=64, max_wait=0.005,
                    pipeline_depth=1, warmup=False,
                    mesh_hosts=hosts if hosts >= 2 else 0,
                )
                async with VerifyEngine(cfg) as eng:
                    futs, k, i = [], 0, 0
                    sizes = [37, 53, 11, 97, 5]
                    while k < len(items_c):
                        n = sizes[i % len(sizes)]
                        i += 1
                        # awaited in the return below (whole-list drain)
                        futs.append(asyncio.ensure_future(  # asyncsan: disable=raw-spawn
                            eng.verify(items_c[k : k + n])
                        ))
                        k += n
                    return [v for f in futs for v in await f]

            fleet_v = await through(4)
            single_v = await through(0)
            mism = [
                (j, shapes[j])
                for j, (g, e) in enumerate(zip(fleet_v, expects))
                if g != e
            ]
            return {
                "items": len(items_c),
                "mismatches": len(mism),
                "single_chip_identical": fleet_v == single_v,
                "clean": not mism and fleet_v == single_v,
                **({"first_mismatches": mism[:5]} if mism else {}),
            }

        async def run() -> dict:
            ways: dict = {}
            for k in ways_list:
                _progress(f"{k}-way fleet...")
                ways[str(k)] = await run_way(k)
            # speedup/efficiency AFTER every way ran (review r13: a
            # baseline-last or baseline-free TPUNODE_BENCH_MESH_WAYS_LIST
            # must not silently skip the acceptance gate)
            base_rate = ways.get("1", {}).get("sigs_per_s")
            for k_str, cell in ways.items():
                k = int(k_str)
                if k != 1 and base_rate:
                    cell["speedup"] = round(
                        cell["sigs_per_s"] / base_rate, 3
                    )
                    cell["efficiency"] = round(
                        cell["sigs_per_s"] / (k * base_rate), 3
                    )
            _progress("campaign parity through the 4-way fleet...")
            camp = await campaign_parity()
            eff4 = ways.get("4", {}).get("efficiency")
            out = {
                "ok": bool(camp["clean"]) and (
                    eff4 is None or eff4 >= 0.8
                ),
                "proxy": "cpu-native",
                "sigs": sigs,
                "unique": len(uniq),
                "submission_items": sub,
                "ways": ways,
                "scaling_floor": 0.8,
                "scaling_at_4": eff4,
                "campaign": camp,
            }
            if not camp["clean"]:
                out["fatal"] = True  # verdict divergence, never mask
                out["error"] = "fleet/single-chip verdict mismatch"
            elif eff4 is not None and eff4 < 0.8:
                out["error"] = (
                    f"4-way scaling {eff4} below the 0.8x-ideal floor"
                )
            elif "4" in ways and eff4 is None:
                # 4-way ran but no 1-way baseline: the floor cannot be
                # evaluated — label it, never report a silent pass
                out["ok"] = False
                out["error"] = (
                    "4-way ran without a 1-way baseline — the 0.8x "
                    "scaling floor was not evaluated"
                )
            return out

        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_mesh_e2e() -> None:
    """Host-affine feed A/B (ISSUE 19): the ingest→extract/pack→dispatch
    →verdict path at 4-way on the cpu-native proxy, affinity ON (keyed
    submissions land in their home host's packer; intake gates on the
    TARGET host's feed depth) vs the central-feed baseline (keyless
    submissions through the shared packer; intake gates on GLOBAL
    unresolved pending — the pre-affinity node policy).

    Both legs get the identical workload (keyed ingest batches, each
    packed in-loop — the extract/pack stage is inside the timed window),
    the identical deferred-intake retry tick, and the identical fault:
    host h0's dispatch stalls ``slow_s`` per lane.  The gates differ the
    way the policies differ: the baseline's global budget is ONE
    pipeline's feed ceiling — fleet-blind, like the node's fixed
    ``MAX_VERIFY_PENDING`` was before affinity — while the affine leg
    budgets the SAME ceiling per host (per-host gates scale intake with
    the fleet by construction).  That asymmetry is the policy under
    test: a per-host gate defers ONLY the slow host's keys while the
    rest of the fleet stays fed; the global gate parks the whole intake
    stream behind the retry timer whenever total unresolved work — most
    of it stuck behind the slow host — trips the one shared budget.  The
    retry tick is 0.25s, deliberately kinder to the baseline than the
    node's real deferral granularity (the 1s mempool scheduler tick).
    Per-host ``feed_idle`` (idle-take fraction) is reported for both legs
    as the starvation signal.  The campaign pool additionally runs
    through the affine path and is cross-checked bit-identical against
    the single-chip verdicts.  Prints one JSON line.
    """
    import asyncio
    import hashlib

    sigs = int(os.environ.get("TPUNODE_BENCH_MESH_E2E_SIGS", 12288))
    hosts = int(os.environ.get("TPUNODE_BENCH_MESH_E2E_HOSTS", 4))
    try:
        from benchmarks.campaign import build_pool
        from benchmarks.common import make_triples, tile
        from tpunode.metrics import metrics
        from tpunode.verify.cpu_native import load_native_verifier
        from tpunode.verify.engine import VerifyConfig, VerifyEngine
        from tpunode.verify.raw import pack_items
        from tpunode.verify.sched import affinity_key

        if load_native_verifier() is None:
            print(json.dumps(
                {"ok": False, "error": "native verifier unavailable"}
            ))
            return
        batch_items = 256  # one ingest batch = one getdata-sized unit
        lane = 256         # small lane target -> tight per-host ceiling
        retry_s = 0.25     # deferred-intake retry tick (see docstring)
        slow_s = 0.05      # injected h0 stall per dispatched lane
        _progress(f"generating {sigs} tiled sigs...")
        uniq = make_triples(min(2048, sigs))
        items = tile(uniq, sigs)
        batches = [
            items[off : off + batch_items]
            for off in range(0, len(items), batch_items)
        ]
        # one stable pseudo-txid per ingest batch: the affinity key is a
        # pure function of the batch index, so both legs and every rerun
        # route identically
        keys = [
            affinity_key(
                hashlib.blake2b(b"mesh-e2e-%d" % i, digest_size=8).digest()
            )
            for i in range(len(batches))
        ]

        def _slow_h0(eng) -> None:
            # the same dispatch seam the scheduler tests use: h0 sleeps
            # in its dispatch worker thread, so its queue backs up while
            # the loop (and the other hosts) keep running
            orig = eng._dispatch_multi

            def wrapper(payloads, target=None, host=None, backend=None):
                if host is not None and host.name == "h0":
                    time.sleep(slow_s)
                if host is None and backend is None:
                    return orig(payloads, target)
                return orig(payloads, target, host=host, backend=backend)

            eng._dispatch_multi = wrapper

        async def run_leg(affine: bool) -> dict:
            metrics.reset()
            cfg = VerifyConfig(
                backend="cpu", batch_size=lane, max_wait=0.005,
                pipeline_depth=1, cpu_threads=1, warmup=False,
                mesh_hosts=hosts,
            )
            async with VerifyEngine(cfg) as eng:
                _slow_h0(eng)
                # the baseline's budget: ONE pipeline's feed ceiling,
                # fleet-blind (pre-affinity MAX_VERIFY_PENDING shape);
                # the affine leg's per-host gates carry the same
                # ceiling PER HOST inside eng.host_pressured()
                limit_global = eng._feed_limit()
                pending = 0
                deferrals = 0
                futs = []

                def _dec(_f, n: int) -> None:
                    nonlocal pending
                    pending -= n

                t0 = time.perf_counter()
                for b, key in zip(batches, keys):
                    if affine:
                        while eng.host_pressured(key):
                            deferrals += 1
                            await asyncio.sleep(retry_s)
                    else:
                        while pending >= limit_global:
                            deferrals += 1
                            await asyncio.sleep(retry_s)
                    raw = pack_items(b)  # extract/pack inside the window
                    pending += len(b)
                    fut = asyncio.ensure_future(  # asyncsan: disable=raw-spawn
                        eng.verify_raw(
                            raw, priority="mempool",
                            affinity=key if affine else None,
                        )
                    )
                    fut.add_done_callback(
                        lambda f, n=len(b): _dec(f, n)
                    )
                    futs.append(fut)
                got = await asyncio.gather(*futs)
                dt = time.perf_counter() - t0
                st = eng.stats()
            n = sum(len(g) for g in got)
            assert n == sigs
            fleet = st["fleet"]
            out = {
                "affine": affine,
                "wall_s": round(dt, 3),
                "sigs_per_s": round(sigs / dt, 1) if dt else 0.0,
                "deferrals": deferrals,
                "feed_idle": fleet["feed_idle"],
                "steals": fleet["steals"],
            }
            if affine:
                out["affinity"] = fleet["affinity"]
            return out

        async def campaign_affine() -> dict:
            # the adversarial pool through the AFFINE path: every chunk
            # keyed, verdicts bit-identical to the single-chip pass (a
            # router that dropped, duplicated, or cross-wired a keyed
            # submission would show up here, not just in throughput)
            import random as _random

            items_c, shapes, expects = build_pool(
                24, _random.Random(0x13E5)
            )

            async def through(fleet_hosts: int) -> list:
                cfg = VerifyConfig(
                    backend="cpu", batch_size=64, max_wait=0.005,
                    pipeline_depth=1, warmup=False,
                    mesh_hosts=fleet_hosts,
                )
                async with VerifyEngine(cfg) as eng:
                    futs, k, i = [], 0, 0
                    sizes = [37, 53, 11, 97, 5]
                    while k < len(items_c):
                        n = sizes[i % len(sizes)]
                        aff = (
                            affinity_key(hashlib.blake2b(
                                b"camp-%d" % i, digest_size=8
                            ).digest())
                            if fleet_hosts else None
                        )
                        i += 1
                        # awaited in the return below (whole-list drain)
                        futs.append(asyncio.ensure_future(  # asyncsan: disable=raw-spawn
                            eng.verify(
                                items_c[k : k + n], affinity=aff
                            )
                        ))
                        k += n
                    return [v for f in futs for v in await f]

            affine_v = await through(hosts)
            single_v = await through(0)
            mism = [
                (j, shapes[j])
                for j, (g, e) in enumerate(zip(affine_v, expects))
                if g != e
            ]
            return {
                "items": len(items_c),
                "mismatches": len(mism),
                "single_chip_identical": affine_v == single_v,
                "clean": not mism and affine_v == single_v,
                **({"first_mismatches": mism[:5]} if mism else {}),
            }

        async def run() -> dict:
            _progress("central-feed baseline leg...")
            central = await run_leg(affine=False)
            _progress("affine leg...")
            affine = await run_leg(affine=True)
            _progress("campaign through the affine path...")
            camp = await campaign_affine()
            ratio = (
                round(affine["sigs_per_s"] / central["sigs_per_s"], 3)
                if central["sigs_per_s"] else None
            )
            floor = 1.25
            out = {
                "ok": bool(camp["clean"])
                and ratio is not None and ratio >= floor,
                "proxy": "cpu-native",
                "sigs": sigs,
                "hosts": hosts,
                "batch_items": batch_items,
                "slow_host": {"host": "h0", "stall_s": slow_s},
                "retry_s": retry_s,
                "central": central,
                "affine": affine,
                "speedup": ratio,
                "speedup_floor": floor,
                "campaign": camp,
            }
            if not camp["clean"]:
                out["fatal"] = True  # verdict divergence, never mask
                out["error"] = "affine-path/single-chip verdict mismatch"
            elif ratio is None:
                out["error"] = "central baseline produced no rate"
            elif ratio < floor:
                out["error"] = (
                    f"affine/central speedup {ratio} below the "
                    f"{floor}x floor"
                )
            return out

        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_serve() -> None:
    """Multi-tenant serve firehose (ISSUE 20): >=1000 simulated clients
    over REAL sockets against a live ServeServer on the cpu-native
    proxy.  Zipf-distributed duplicates over a ~2048-unique signed-row
    pool (the shared verdict cache must absorb the repeats at zero
    verify cost), 8 tenants across all four priority classes.  Two
    legs: (1) the firehose — per-class verdict-latency p50/p99, cache
    hit-rate, and the CONSERVATION pin: the engine verifies each unique
    row exactly once (first submitter wins, duplicates coalesce/hit),
    and every verdict matches the pool's known validity pattern — any
    divergence is ``fatal`` exactly like a headline verdict mismatch;
    (2) the induced-burn leg — the server's SLO hook reports a
    fast-window burn, and ONLY bulk-class tenants may shed while
    block-class p99 stays inside the DEFAULT_SLOS block objective.  A
    receipt log rides the whole run in a tempdir and must audit clean
    (hash chain + CRC walk); its per-append overhead is reported.
    Prints one JSON line.
    """
    import asyncio
    import contextlib
    import itertools
    import random
    import tempfile

    clients_n = int(os.environ.get("TPUNODE_BENCH_SERVE_CLIENTS", 1000))
    frames_per = int(os.environ.get("TPUNODE_BENCH_SERVE_FRAMES", 3))
    items_per = int(os.environ.get("TPUNODE_BENCH_SERVE_ITEMS", 12))
    try:
        from benchmarks.common import make_triples
        from tpunode.metrics import metrics
        from tpunode.receipts import ReceiptLog, audit
        from tpunode.serve import ServeServer, TenantConfig
        from tpunode.slo import DEFAULT_SLOS
        from tpunode.verify.cpu_native import load_native_verifier
        from tpunode.verify.engine import VerifyConfig, VerifyEngine

        if load_native_verifier() is None:
            print(json.dumps(
                {"ok": False, "error": "native verifier unavailable"}
            ))
            return
        uniq_n = 2048
        invalid_every = 16
        _progress(f"generating {uniq_n} unique signed rows...")
        triples = make_triples(uniq_n, invalid_every=invalid_every)
        rows = [
            [
                z.to_bytes(32, "big").hex(),
                (
                    b"\x04"
                    + q.x.to_bytes(32, "big")
                    + q.y.to_bytes(32, "big")
                ).hex(),
                (r.to_bytes(32, "big") + s.to_bytes(32, "big")).hex(),
            ]
            for (q, z, r, s) in triples
        ]
        # make_triples corrupts every invalid_every-th message: the
        # expected verdict per row index is known a priori, so every
        # client checks every reply bit (the conservation tally's twin)
        expected = [
            i % invalid_every != invalid_every - 1 for i in range(uniq_n)
        ]
        # Zipf(1.1) over the pool: head rows repeat constantly (cache
        # fodder), the tail keeps fresh verify work arriving
        cum_w = list(itertools.accumulate(
            1.0 / (i + 1) ** 1.1 for i in range(uniq_n)
        ))
        classes = ("block", "mempool", "ibd", "bulk")
        tenants = [
            TenantConfig(
                name=f"t{i}", token=f"tok-{i}",
                priority=classes[i % len(classes)],
                rate=1e9, burst=1e9, max_inflight=8192,
            )
            for i in range(8)
        ]
        block_slo = next(
            s for s in DEFAULT_SLOS
            if s.kind == "latency" and s.priority == "block"
        )

        async def run() -> dict:
            metrics.reset()
            burn: dict = {"on": False}
            counted = {"verify_items": 0}
            tmp = tempfile.mkdtemp(prefix="tpunode-serve-bench-")
            cfg = VerifyConfig(
                backend="cpu", batch_size=256, max_wait=0.002,
                pipeline_depth=1, cpu_threads=1, warmup=False,
            )
            receipts = ReceiptLog(tmp)
            async with VerifyEngine(cfg) as eng:
                orig_verify = eng.verify

                async def counting_verify(items, **kw):
                    counted["verify_items"] += len(items)
                    return await orig_verify(items, **kw)

                eng.verify = counting_verify
                async with ServeServer(
                    eng, tenants, port=0,
                    slo_burning=lambda: (
                        ["verdict-latency-block"] if burn["on"] else []
                    ),
                    receipts=receipts,
                ) as srv:
                    lat: dict = {}
                    sem = asyncio.Semaphore(250)  # fd + loop sanity

                    async def one_client(
                        ci: int, leg: str, tally: dict
                    ) -> None:
                        t = tenants[ci % len(tenants)]
                        rng = random.Random(0x5E12C1 ^ (ci * 2654435761))
                        async with sem:
                            reader, writer = await asyncio.open_connection(
                                "127.0.0.1", srv.port
                            )
                            try:
                                for fi in range(frames_per):
                                    idxs = rng.choices(
                                        range(uniq_n), cum_weights=cum_w,
                                        k=items_per,
                                    )
                                    frame = {
                                        "tenant": t.name, "token": t.token,
                                        "items": [rows[j] for j in idxs],
                                        "id": fi,
                                    }
                                    data = json.dumps(
                                        frame, separators=(",", ":")
                                    ).encode()
                                    t0 = time.perf_counter()
                                    writer.write(
                                        len(data).to_bytes(4, "big") + data
                                    )
                                    await writer.drain()
                                    hdr = await reader.readexactly(4)
                                    body = await reader.readexactly(
                                        int.from_bytes(hdr, "big")
                                    )
                                    dt = time.perf_counter() - t0
                                    reply = json.loads(body)
                                    lat.setdefault(
                                        (leg, t.priority), []
                                    ).append(dt)
                                    if reply.get("ok"):
                                        vs = reply["verdicts"]
                                        tally["verdicts"] += len(vs)
                                        tally["cached"] += reply.get(
                                            "cached", 0
                                        )
                                        tally["seen"].update(idxs)
                                        tally["wrong"] += sum(
                                            1
                                            for j, v in zip(idxs, vs)
                                            if bool(v) != expected[j]
                                        )
                                    elif reply.get("error") == "shed":
                                        shed = tally["shed_by_class"]
                                        shed[t.priority] = (
                                            shed.get(t.priority, 0)
                                            + len(reply.get("verdicts") or ())
                                        )
                                    elif reply.get("error") == "throttled":
                                        tally["throttled"] += 1
                                    else:
                                        tally["errors"] += 1
                            finally:
                                with contextlib.suppress(Exception):
                                    writer.close()
                                    await writer.wait_closed()

                    def fresh_tally() -> dict:
                        return {
                            "verdicts": 0, "cached": 0, "wrong": 0,
                            "throttled": 0, "errors": 0,
                            "shed_by_class": {}, "seen": set(),
                        }

                    _progress(f"firehose leg: {clients_n} clients...")
                    fire = fresh_tally()
                    t0 = time.perf_counter()
                    await asyncio.gather(*(
                        one_client(ci, "fire", fire)
                        for ci in range(clients_n)
                    ))
                    fire_wall = time.perf_counter() - t0
                    verified_fire = counted["verify_items"]

                    burn_clients = max(256, len(tenants) * 16)
                    _progress(
                        f"induced-burn leg: {burn_clients} clients..."
                    )
                    burn["on"] = True
                    bleg = fresh_tally()
                    await asyncio.gather(*(
                        one_client(ci, "burn", bleg)
                        for ci in range(burn_clients)
                    ))
                    burn["on"] = False
                    srv_stats = srv.stats()
            receipts.close()
            verdict = audit(tmp)

            def pcts(key) -> dict:
                xs = sorted(lat.get(key, ()))
                if not xs:
                    return {"p50": None, "p99": None, "n": 0}
                return {
                    "p50": round(xs[len(xs) // 2], 4),
                    "p99": round(xs[min(len(xs) - 1, int(len(xs) * 0.99))], 4),
                    "n": len(xs),
                }

            # conservation: every unique row that reached admission was
            # verified EXACTLY once during the firehose; everything else
            # (the Zipf mass) came out of the shared cache
            conserve_ok = (
                verified_fire == len(fire["seen"])
                and fire["cached"] + verified_fire == fire["verdicts"]
            )
            wrong = fire["wrong"] + bleg["wrong"]
            shed_classes = sorted(bleg["shed_by_class"])
            burn_block_p99 = pcts(("burn", "block"))["p99"]
            shed_ok = (
                bool(bleg["shed_by_class"])
                and shed_classes == ["bulk"]
                and not fire["shed_by_class"]
            )
            p99_ok = (
                burn_block_p99 is not None
                and burn_block_p99 <= block_slo.threshold
            )
            appended = metrics.get("receipts.appended")
            out = {
                "ok": (
                    wrong == 0 and conserve_ok and shed_ok and p99_ok
                    and bool(verdict["ok"]) and fire["errors"] == 0
                    and bleg["errors"] == 0
                ),
                "proxy": "cpu-native",
                "clients": clients_n + burn_clients,
                "tenants": len(tenants),
                "unique_rows": uniq_n,
                "frames_per_client": frames_per,
                "items_per_frame": items_per,
                "firehose": {
                    "wall_s": round(fire_wall, 3),
                    "verdicts": fire["verdicts"],
                    "verified_unique": verified_fire,
                    "unique_submitted": len(fire["seen"]),
                    "cache_hits": fire["cached"],
                    "cache_hit_rate": round(
                        fire["cached"] / fire["verdicts"], 4
                    ) if fire["verdicts"] else None,
                    "throttled": fire["throttled"],
                    "wire_errors": fire["errors"],
                },
                "latency": {
                    cls: pcts(("fire", cls)) for cls in classes
                },
                "burn_leg": {
                    "shed_by_class": bleg["shed_by_class"],
                    "shed_classes": shed_classes,
                    "block_p99": burn_block_p99,
                    "block_objective_s": round(block_slo.threshold, 4),
                    "verdicts": bleg["verdicts"],
                    "wire_errors": bleg["errors"],
                },
                "conservation": {
                    "ok": conserve_ok,
                    "verified": verified_fire,
                    "unique_submitted": len(fire["seen"]),
                },
                "receipts": {
                    "records": verdict["records"],
                    "segments": verdict["segments"],
                    "audit_ok": bool(verdict["ok"]),
                    "findings": verdict["findings"][:5],
                    "append_ms_avg": round(
                        1e3 * metrics.get("receipts.append_seconds")
                        / appended, 4
                    ) if appended else None,
                },
                "spend_by_tenant": srv_stats.get("spend", {}).get(
                    "by_tenant", {}
                ),
            }
            if wrong:
                out["fatal"] = True  # verdict divergence, never mask
                out["error"] = (
                    f"{wrong} served verdicts diverged from the pool's "
                    "known validity pattern"
                )
            elif not conserve_ok:
                out["fatal"] = True
                out["error"] = (
                    "verdict conservation broke: "
                    f"verified {verified_fire} != unique "
                    f"{len(fire['seen'])} (or hits+verified != verdicts)"
                )
            elif not verdict["ok"]:
                out["error"] = "receipt audit found findings"
            elif not shed_ok:
                out["error"] = (
                    f"shed classes {shed_classes or 'none'} — expected "
                    "exactly ['bulk'] under burn and none before it"
                )
            elif not p99_ok:
                out["error"] = (
                    f"block-class p99 {burn_block_p99}s breached the "
                    f"{block_slo.threshold:.3f}s objective under burn"
                )
            return out

        print(json.dumps(asyncio.run(run())))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_mesh_device() -> None:
    """One device-mesh sharding sample (ISSUE 13): raw-batch dispatch
    through ``multichip.dispatch_raw_sharded`` at
    ``TPUNODE_BENCH_MESH_WAYS``-way sharding over the visible devices
    (more ways than devices is an error), cross-checked against the C++
    verifier, timed at steady state.  ``TPUNODE_BENCH_BATCH`` is the
    whole-mesh batch.  The result names the device it ran on: on a CPU
    mesh the timing says how fast XLA's CPU backend is."""
    ways = int(os.environ.get("TPUNODE_BENCH_MESH_WAYS", 8))
    batch = int(os.environ.get("TPUNODE_BENCH_BATCH", 4096))
    iters = int(os.environ.get("TPUNODE_BENCH_ITERS", TIMED_ITERS))
    try:
        import jax

        from tpunode.verify.engine import enable_compile_cache

        enable_compile_cache()
        t0 = time.perf_counter()
        jax.devices()
        init_s = time.perf_counter() - t0
        from benchmarks.common import device_kind, make_triples, tile
        from tpunode.verify.cpu_native import load_native_verifier
        from tpunode.verify.kernel import collect_verdicts
        from tpunode.verify.multichip import (
            dispatch_raw_sharded,
            make_hybrid_mesh,
        )
        from tpunode.verify.raw import pack_items

        mesh = make_hybrid_mesh(ways, 1)
        base = make_triples(min(UNIQUE, batch))
        raw = pack_items(tile(base, batch))
        _progress(f"compiling {ways}-way sharded program at batch {batch}...")
        t0 = time.perf_counter()
        got = collect_verdicts(
            *dispatch_raw_sharded(raw, mesh, pad_to=batch)
        )[: len(base)]
        compile_s = time.perf_counter() - t0
        if got != load_native_verifier().verify_batch(base):
            print(json.dumps(
                {"ok": False, "fatal": True,
                 "error": "mesh/oracle verdict mismatch"}
            ))
            return
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ok, _count = dispatch_raw_sharded(raw, mesh, pad_to=batch)
            ok.block_until_ready()
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times)
        print(json.dumps({
            "ok": True,
            "rate": batch / dt,
            "device": device_kind(),
            "mesh_ways": ways,
            "batch": batch,
            "step_ms": round(dt * 1e3, 3),
            "compile_s": round(compile_s, 1),
            "init_s": round(init_s, 1),
        }))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_ibd() -> None:
    """Long-IBD replay A/B over the persistent store (ISSUE 11): a bare
    Node syncs a fakenet chain through the REAL fetch planner
    (NodeConfig.ibd) — no embedder pushes anywhere — measured three ways:

    * ``ingest_native``: verify engine ON (cpu-native rung), sharded
      extraction + delta-blob UTXO connect — e2e blocks/s and sigs/s;
    * ``connect_native``: verify engine OFF — the pure block-ingest path
      (wire → parse → UTXO connect), the block-connect hot path in
      isolation;
    * ``kill9``: a child process killed mid-sync over a LogKV store, then
      restarted — proving the restart resumes from the watermark with
      ZERO re-verified (and zero re-fetched) blocks.

    Prints one JSON line.
    """
    import asyncio
    import shutil
    import signal
    import subprocess
    import tempfile

    # 129-tx blocks (incl. coinbase) put the BLOCK regions over the
    # 2*MIN_SHARD_TXS sharding threshold, so the native leg exercises the
    # per-tx-range worker-pool split the section exists to measure
    n_blocks = int(os.environ.get("TPUNODE_BENCH_IBD_BLOCKS", 240))
    txs_per_block = int(os.environ.get("TPUNODE_BENCH_IBD_TXS", 128))
    inputs_per_tx = int(os.environ.get("TPUNODE_BENCH_IBD_INPUTS", 1))
    kill_blocks = int(os.environ.get("TPUNODE_BENCH_IBD_KILL_BLOCKS", 1500))
    try:
        from benchmarks.txgen import gen_chain, synth_prevout
        from tpunode import (
            BCH_REGTEST,
            IbdConfig,
            Node,
            NodeConfig,
            Publisher,
            TxVerdict,
        )
        from tpunode.store import LogKV
        from tpunode.verify.engine import VerifyConfig

        from tpunode import txextract

        if not txextract.have_native_extract():
            print(json.dumps(
                {"ok": False, "error": "native extractor unavailable"}
            ))
            return
        net = BCH_REGTEST
        _progress(
            f"generating {n_blocks}-block chain x{txs_per_block} txs..."
        )
        all_blocks = gen_chain(
            net, n_blocks, txs_per_block, inputs_per_tx=inputs_per_tx,
            cache=(
                f"ibd_bench_{n_blocks}x{txs_per_block}"
                f"x{inputs_per_tx}.bin"
            ),
        )
        n_sigs = sum(
            len(tx.inputs) for b in all_blocks for tx in b.txs[1:]
        )

        async def sync_once(verify: bool, store_dir: str, blocks=None):
            blocks = all_blocks if blocks is None else blocks
            count = len(blocks)
            """One full planner-driven sync over a fresh LogKV store."""
            from tests.fakenet import dummy_peer_connect, poll_until

            store = LogKV(os.path.join(store_dir, "kv.log"))
            pub = Publisher(name="bench-ibd", maxsize=None)
            cfg = NodeConfig(
                net=net, store=store, pub=pub,
                peers=["[::1]:18555"], discover=False,
                connect=lambda sa: dummy_peer_connect(net, blocks),
                verify=(
                    VerifyConfig(backend="cpu", max_wait=0.005)
                    if verify else None
                ),
                prevout_lookup=synth_prevout if verify else None,
                utxo=True,
                ibd=IbdConfig(batch_blocks=16, tick_interval=0.05),
            )
            verdicts = 0
            t0 = time.perf_counter()
            async with pub.subscription() as events:
                async with Node(cfg) as node:
                    async def watch():
                        nonlocal verdicts
                        while True:
                            ev = await events.receive()
                            if isinstance(ev, TxVerdict):
                                verdicts += 1
                    task = asyncio.ensure_future(watch())  # asyncsan: disable=raw-spawn (bench observer, cancelled below)
                    try:
                        await poll_until(
                            lambda: node.utxo.height == count,
                            timeout=600, what="ibd sync",
                        )
                        if verify:
                            total = count * (txs_per_block + 1)
                            await poll_until(
                                lambda: verdicts >= total,
                                timeout=120, what="all verdicts",
                            )
                    finally:
                        task.cancel()
                    dt = time.perf_counter() - t0
                    fetched = node.ibd.stats()["fetched_blocks"]
            store.close()
            sigs = sum(
                len(tx.inputs) for b in blocks for tx in b.txs[1:]
            )
            return {
                "wall_s": round(dt, 3),
                "blocks_per_s": round(count / dt, 1),
                "txs_per_s": round(
                    count * (txs_per_block + 1) / dt, 1
                ),
                "sigs_per_s": round(sigs / dt, 1) if verify else None,
                "verdicts": verdicts,
                "fetched_blocks": fetched,
            }

        async def run_legs() -> dict:
            out: dict = {"ok": True, "proxy": "cpu-native",
                         "blocks": n_blocks, "txs_per_block": txs_per_block,
                         "inputs_per_tx": inputs_per_tx, "sigs": n_sigs}
            # untimed FULL-SIZE warmup: the first full-scale sync in a
            # process pays one-off costs (native lib loads, engine
            # warmup, allocator/heap growth at the working-set size)
            # that would otherwise be billed to whichever timed leg runs
            # first — a 40-block mini-warmup measurably does NOT cover
            # them (the first 300-block leg still ran ~4x slow)
            _progress("warmup sync (untimed, full size)...")
            d = tempfile.mkdtemp(prefix="ibd_warmup_")
            try:
                await sync_once(True, d)
            finally:
                shutil.rmtree(d, ignore_errors=True)
            legs = (
                # the ingest leg runs twice, best kept: host-load drift
                # on a shared box swings a single pass ±30% (the PERF r6
                # round-robin lesson, applied cheaply)
                ("ingest_native", True, 2,
                 "verify on, sharded native extract + C++ connect"),
                ("connect_native", False, 1,
                 "no verify: wire -> C++ one-pass UTXO connect"),
            )
            for key, verify, reps, note in legs:
                _progress(f"{key}: {note}...")
                best = None
                for _ in range(reps):
                    d = tempfile.mkdtemp(prefix=f"ibd_{key}_")
                    try:
                        leg = await sync_once(verify, d)
                    finally:
                        shutil.rmtree(d, ignore_errors=True)
                    if best is None or leg["wall_s"] < best["wall_s"]:
                        best = leg
                best["note"] = note
                best["runs"] = reps
                out[key] = best
            return out

        section = asyncio.run(run_legs())

        # -- kill -9 leg ----------------------------------------------------
        _progress(f"kill -9 leg: {kill_blocks}-block child sync...")
        d = tempfile.mkdtemp(prefix="ibd_kill9_")
        try:
            child_env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                TPUNODE_IBD_CHILD_DIR=d,
                TPUNODE_IBD_CHILD_BLOCKS=str(kill_blocks),
            )
            def spawn():
                return subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--ibd-child"],
                    stdout=subprocess.PIPE, text=True, env=child_env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
            # phase 1: kill mid-sync once the watermark passes ~40%
            p = spawn()
            killed_at = None
            deadline = time.monotonic() + 240
            for line in p.stdout:
                if time.monotonic() > deadline:
                    break
                if line.startswith("WM "):
                    wm = int(line.split()[1])
                    if wm >= kill_blocks * 2 // 5:
                        killed_at = wm
                        os.kill(p.pid, signal.SIGKILL)
                        break
                elif line.startswith("DONE"):
                    break  # synced before we could kill: still a result
            p.wait()
            if killed_at is None:
                section["kill9"] = {
                    "ok": False,
                    "error": "child finished before the kill window",
                }
            else:
                # phase 2: restart over the same store, run to completion
                p2 = spawn()
                report = None
                for line in p2.stdout:
                    if line.startswith("DONE "):
                        report = json.loads(line[5:])
                p2.wait()
                if report is None:
                    section["kill9"] = {
                        "ok": False, "error": "restart child died",
                    }
                else:
                    resumed = report["start_watermark"]
                    expected = (kill_blocks - resumed) * 2  # tx + coinbase
                    # "zero re-verification" is measured against the
                    # RESUMED watermark: a kill mid-write may lose the
                    # last un-synced record (torn tail, truncated on
                    # replay), but everything below the watermark the
                    # store DID resume from must cost nothing again.
                    section["kill9"] = {
                        "ok": (
                            resumed > 0
                            and report["final_watermark"] == kill_blocks
                            and report["verify_txs"] == expected
                            and report["fetched_blocks"]
                            == kill_blocks - resumed
                        ),
                        "killed_at_watermark": killed_at,
                        "resumed_from_watermark": resumed,
                        "final_watermark": report["final_watermark"],
                        "reverified_blocks": max(
                            0,
                            (report["verify_txs"] - expected) // 2,
                        ),
                        "refetched_blocks": max(
                            0,
                            report["fetched_blocks"]
                            - (kill_blocks - resumed),
                        ),
                    }
                    if not section["kill9"]["ok"]:
                        section["ok"] = False
                        section["error"] = "kill -9 leg failed"
        finally:
            shutil.rmtree(d, ignore_errors=True)
        print(json.dumps(section))
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}))


def _worker_ibd_child() -> None:
    """The kill -9 leg's child: one planner-driven sync (verify engine on,
    cpu-native rung) over a persistent LogKV store in
    TPUNODE_IBD_CHILD_DIR, printing ``WM <height>`` as the watermark
    advances (the parent kills on this signal) and a final ``DONE
    {json}`` report.  Restarted over the same directory it resumes from
    the persisted watermark."""
    import asyncio

    from benchmarks.txgen import gen_chain, synth_prevout
    from tests.fakenet import dummy_peer_connect, poll_until
    from tpunode import (
        BCH_REGTEST, IbdConfig, Node, NodeConfig, Publisher,
    )
    from tpunode.metrics import metrics
    from tpunode.store import LogKV
    from tpunode.verify.engine import VerifyConfig

    d = os.environ["TPUNODE_IBD_CHILD_DIR"]
    n_blocks = int(os.environ["TPUNODE_IBD_CHILD_BLOCKS"])
    net = BCH_REGTEST
    blocks = gen_chain(
        net, n_blocks, 1, cache=f"ibd_kill_{n_blocks}x1.bin"
    )

    async def run():
        store = LogKV(os.path.join(d, "kv.log"), fsync=False)
        pub = Publisher(name="ibd-child", maxsize=None)
        cfg = NodeConfig(
            net=net, store=store, pub=pub,
            peers=["[::1]:18555"], discover=False,
            connect=lambda sa: dummy_peer_connect(net, blocks),
            verify=VerifyConfig(backend="cpu", max_wait=0.005),
            prevout_lookup=synth_prevout,
            utxo=True,
            ibd=IbdConfig(batch_blocks=16, tick_interval=0.05),
        )
        async with pub.subscription():
            async with Node(cfg) as node:
                start_wm = node.utxo.height
                last = [start_wm]

                async def report_progress():
                    while True:
                        wm = node.utxo.height
                        if wm != last[0]:
                            last[0] = wm
                            print(f"WM {wm}", flush=True)
                        await asyncio.sleep(0.01)

                task = asyncio.ensure_future(report_progress())  # asyncsan: disable=raw-spawn (child progress pipe, cancelled below)
                try:
                    await poll_until(
                        lambda: node.utxo.height == n_blocks,
                        timeout=600, what="child sync",
                    )
                finally:
                    task.cancel()
                print("DONE " + json.dumps({
                    "start_watermark": start_wm,
                    "final_watermark": node.utxo.height,
                    "verify_txs": int(metrics.get("node.verify_txs")),
                    "fetched_blocks": node.ibd.stats()["fetched_blocks"],
                }), flush=True)
        store.close()

    asyncio.run(run())


def _worker_observability() -> None:
    """Observability-overhead micro-bench (ISSUE 16).

    Populates a realistic registry (~100 unlabeled series, an 8-host
    fleet's labeled gauges, a busy histogram), then measures: the
    timeline sampler's per-tick cost (median), the off-switch tick cost
    (must be ~an attribute read), and one flight-recorder bundle build.
    Never imports jax — timeseries/blackbox are stdlib-only by contract.
    """
    try:
        import statistics as _stats

        from tpunode.blackbox import FlightRecorder, FlightRecorderConfig
        from tpunode.metrics import metrics
        from tpunode.timeseries import Timeline

        from tpunode.verify.sched import host_names  # jax-free

        for i in range(100):
            metrics.inc("bench.obs_series_%d" % i, i + 1)
        for h, name in enumerate(host_names(8)):
            host = {"host": name}
            metrics.set_gauge("sched.host_depth", float(h), labels=host)
            metrics.set_gauge("verify.breaker_state", 0.0, labels=host)
            metrics.set_gauge("mesh.host_chips", 4.0, labels=host)
        for i in range(64):
            metrics.observe("verify.occupancy", (i % 20) / 20.0)

        def tick_median(tl: "Timeline", n: int = 300) -> float:
            xs = []
            for _ in range(n):
                t0 = time.perf_counter()
                tl.tick()
                xs.append(time.perf_counter() - t0)
            return _stats.median(xs)

        timeline = Timeline(interval=1.0, disabled=False)
        timeline.tick()  # warm the rings (first tick allocates deques)
        tick_s = tick_median(timeline)
        off = Timeline(interval=1.0, disabled=True)
        off_s = tick_median(off)

        recorder = FlightRecorder(
            FlightRecorderConfig(min_interval=0.0), timeline=timeline
        )
        t0 = time.perf_counter()
        bundle = recorder.record("bench.observability", force=True)
        build_ms = (time.perf_counter() - t0) * 1e3

        # SLO engine (ISSUE 17): evaluator tick cost (enabled + the
        # off-switch), and burn-detection latency — how many 1s ticks a
        # synthetic dispatch stall needs to page against a 100-tick
        # healthy baseline (deterministic: explicit now= timestamps).
        from tpunode.events import EventLog
        from tpunode.slo import SloEvaluator

        def slo_tick_median(ev, base: float, n: int = 300) -> float:
            xs = []
            for i in range(n):
                t0 = time.perf_counter()
                ev.tick(now=base + i)
                xs.append(time.perf_counter() - t0)
            return _stats.median(xs)

        slo_tick_s = slo_tick_median(
            SloEvaluator(registry=metrics, log_=EventLog(), disabled=False),
            base=1_000.0,
        )
        slo_off_s = slo_tick_median(
            SloEvaluator(defs=None, registry=metrics, log_=EventLog()),
            base=2_000.0,
        )
        det_log = EventLog()
        det = SloEvaluator(registry=metrics, log_=det_log, disabled=False)
        for i in range(100):
            det.tick(now=50_000.0 + i)  # healthy baseline
        metrics.set_gauge("watchdog.stalled", 1.0)  # the wedged dispatch
        det_ticks = 0
        for i in range(100, 400):
            det.tick(now=50_000.0 + i)
            det_ticks += 1
            if det_log.counts().get("slo.burn"):
                break
        metrics.set_gauge("watchdog.stalled", 0.0)

        print(
            json.dumps(
                {
                    "ok": True,
                    "sampler": {
                        "tick_us_p50": round(tick_s * 1e6, 2),
                        "disabled_tick_us_p50": round(off_s * 1e6, 4),
                        "series": timeline.stats()["series"],
                    },
                    "blackbox": {
                        "build_ms": round(build_ms, 3),
                        "bundle_keys": sorted(bundle or {}),
                    },
                    "slo": {
                        "tick_us_p50": round(slo_tick_s * 1e6, 2),
                        "disabled_tick_us_p50": round(slo_off_s * 1e6, 4),
                        "burn_detection": {
                            "ticks": det_ticks,
                            "seconds": round(det_ticks * det.interval, 1),
                        },
                    },
                }
            )
        )
    except Exception as e:  # noqa: BLE001 — one JSON line
        print(
            json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]})
        )


if __name__ == "__main__":
    if "--mempool" in sys.argv:
        _worker_mempool()
    elif "--chaos" in sys.argv:
        _worker_chaos()
    elif "--recovery" in sys.argv:
        _worker_recovery()
    elif "--pipeline" in sys.argv:
        _worker_pipeline()
    elif "--ibd-child" in sys.argv:
        _worker_ibd_child()
    elif "--ibd" in sys.argv:
        _worker_ibd()
    elif "--mesh-device" in sys.argv:
        _worker_mesh_device()
    elif "--mesh-e2e" in sys.argv:
        _worker_mesh_e2e()
    elif "--serve" in sys.argv:
        _worker_serve()
    elif "--mesh" in sys.argv:
        _worker_mesh()
    elif "--observability" in sys.argv:
        _worker_observability()
    else:
        _worker_bench()
