"""What every cell shares: finding a cell's files, the node under test, the
verdict sink, the window's bookkeeping, and the comparison that decides
``correct``.

A cell is ``<config>.<traffic>``.  The harness finds
``chipbench/configs/<config>.json``, ``chipbench/traffic/<traffic>.json``,
the driver module the traffic file names (``chipbench/drivers/``), and the
per-layer metrics ``BENCHMARK.json`` lists for the cell
(``chipbench/metrics/<name>.json`` + the reader each names under
``chipbench/readers/``).  Nothing in this file names a cell, a
configuration, a mix or a metric.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import copy
import importlib
import json
import multiprocessing
import os
import random
import resource
import sys
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

GUARANTEE_COUNTERS = (
    "verify.cpu_items", "verify.oracle_items", "verify.failovers",
    "verify.dispatch_errors",
)


def note(ctx, msg: str) -> None:
    print(f"[chipbench +{time.monotonic() - ctx.t_start:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def line(kind: str, **fields) -> None:
    """An earlier line of stdout: detail that is not judged."""
    print(json.dumps({"line": kind, **fields}), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def deep_merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


@dataclass
class Rehearsal:
    """A CPU rehearsal (tests only; the command line cannot ask for one):
    overrides merged over the configuration and the traffic file, no look
    for a chip, no device metric in the result."""

    config: dict = field(default_factory=dict)
    traffic: dict = field(default_factory=dict)


@dataclass
class Ctx:
    workload: dict  # the cell's entry in BENCHMARK.json
    bench: dict  # all of BENCHMARK.json
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: Rehearsal | None
    t_start: float
    device: dict = field(default_factory=dict)
    pool: object = None
    run_dir: str = ""
    compiles: list = field(default_factory=list)  # monotonic stamps

    def rng(self, what: str) -> random.Random:
        return random.Random(f"{self.seed}:{what}")


def load_cell(name: str, root: str = ROOT) -> tuple:
    bench = load_json(root, "BENCHMARK.json")
    cells = {wl["name"]: wl for wl in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    wl = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = load_json(root, cfg_entry["file"])
    traffic = load_json(root, "chipbench", "traffic", wl["traffic"] + ".json")
    return bench, wl, config, traffic


# ---- worker pool ------------------------------------------------------------


def start_pool(ctx: Ctx):
    """Spawned, jax-free workers: generation before the window, the
    reference after it."""
    n = max(1, min(12, (os.cpu_count() or 4) - 3))
    ctx.pool = multiprocessing.get_context("spawn").Pool(n)
    return ctx.pool


async def gather_jobs(ctx: Ctx, fn, jobs: list) -> list:
    res = ctx.pool.map_async(fn, jobs, chunksize=1)
    while not res.ready():  # polled, so that a cancelled run leaves no thread
        await asyncio.sleep(0.02)
    return res.get()


# ---- the node under test ----------------------------------------------------


def require_native() -> None:
    """The three native libraries, built from source where missing.  The
    node would fall back to its Python paths without them; a benchmark run
    does not."""
    from tpunode import native, txextract
    from tpunode.verify import cpu_native

    if not txextract.have_native_extract():
        raise SystemExit("chipbench: native/txextract did not build or load")
    if cpu_native.load_native_verifier() is None:
        raise SystemExit("chipbench: native/secp256k1 did not build or load")
    if native.load_kvstore_lib() is None:
        raise SystemExit("chipbench: native/kvstore did not build or load")


def make_node(ctx: Ctx, oracle, ports: list):
    """The configuration file's deployment as a ``Node``.  The engine
    starts warming the moment it is constructed."""
    from tpunode import Node, NodeConfig, Publisher
    from tpunode.ibd import IbdConfig
    from tpunode.mempool import MempoolConfig
    from tpunode.params import NETWORKS
    from tpunode.store import LogKV
    from tpunode.verify.engine import VerifyConfig

    c = ctx.config
    net = NETWORKS[c["network"]["name"]]
    if net.magic != int(c["network"]["magic"], 16):
        raise SystemExit("chipbench: the program's network parameters differ "
                         "from the configuration file's")
    node_c = c["node"]
    if node_c["store"]["kind"] != "logkv":
        raise SystemExit("chipbench: unknown store kind")
    store = LogKV(os.path.join(ctx.run_dir, "store", "node.log"),
                  fsync=bool(node_c["store"]["fsync"]))
    pub = Publisher(name="chipbench", maxsize=None)  # lossless: exact counts
    cfg = NodeConfig(
        net=net, store=store, pub=pub,
        peers=[f"127.0.0.1:{p}" for p in ports],
        max_peers=max(20, len(ports)),
        discover=False,
        verify=VerifyConfig(**c["verify"]),
        prevout_lookup=oracle,
        utxo=bool(node_c["utxo"]),
        ibd=IbdConfig(**node_c["ibd"]) if node_c.get("ibd") else None,
        mempool=(MempoolConfig(**node_c["mempool"])
                 if node_c.get("mempool") is not None else None),
    )
    return Node(cfg), pub, store


async def await_engine(ctx: Ctx, node, timeout: float = 1100.0) -> float:
    """Wait for the engine's own warm-up thread; returns when it is ready."""
    eng = node.verify_engine
    if eng.cfg.backend != "tpu":
        return 0.0
    t0 = time.monotonic()
    while eng.device_state in ("cold", "warming"):
        if time.monotonic() - t0 > timeout:
            raise SystemExit("chipbench: engine warm-up timed out")
        await asyncio.sleep(0.1)
    if eng.device_state != "ready":
        raise SystemExit(f"chipbench: engine warm-up failed: {eng.stats()}")
    return time.monotonic() - t0


def watch_compiles(ctx: Ctx) -> None:
    """Stamp every backend compile, so that one inside the window shows."""
    import jax

    def on_duration(name: str, _secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            ctx.compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_duration)


# ---- the verdict sink -------------------------------------------------------


class Sink:
    """Every ``TxVerdict`` the node publishes, with the moment a subscriber
    saw it."""

    def __init__(self):
        self.t: list = []
        self.nsigs: list = []
        self.txids: list = []
        self.verdicts: list = []
        self.errors = 0
        self.shed = 0
        self.listeners: list = []

    def add(self, ev, now: float) -> None:
        self.t.append(now)
        self.nsigs.append(len(ev.verdicts))
        self.txids.append(ev.txid)
        self.verdicts.append(tuple(bool(v) for v in ev.verdicts))
        if ev.error or ev.valid != all(ev.verdicts):
            self.errors += 1
        for fn in self.listeners:
            fn(ev.txid, now)


async def consume(events, sink: Sink) -> None:
    from tpunode.node import TxVerdict, VerifyShed

    while True:
        batch = [await events.receive()]
        batch += events.drain_nowait()
        now = time.monotonic()
        for ev in batch:
            if type(ev) is TxVerdict:
                sink.add(ev, now)
            elif type(ev) is VerifyShed:
                sink.shed += ev.dropped_txs


async def until(cond, seconds: float, what: str, step: float = 0.01) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        if time.monotonic() > deadline:
            raise SystemExit(f"chipbench: timed out after {seconds:.0f}s "
                             f"waiting for {what}")
        await asyncio.sleep(step)


# ---- the window -------------------------------------------------------------


def cpu_seconds() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


@dataclass
class Mark:
    t: float
    cpu: float
    counters: dict
    n_verdicts: int


def mark(sink: Sink) -> Mark:
    from tpunode.metrics import metrics

    return Mark(time.monotonic(), cpu_seconds(), metrics.snapshot(),
                len(sink.t))


async def traced(ctx: Ctx, start_at: float, seconds: float) -> str:
    """Profile the ``seconds`` from ``start_at`` on into the run directory.

    The program's ``profile_to`` starts the profiler with JAX's defaults,
    which trace every Python call: on this host-bound path that cut the
    rate to a third (my chip run, PR 23).  The capture therefore goes
    through ``profile_to`` (so that the program's spans annotate it) with
    ``jax.profiler.trace`` wrapped to switch the Python tracer off."""
    import jax

    from tpunode.trace import profile_to

    out = os.path.join(ctx.run_dir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    plain = jax.profiler.trace

    def capture() -> None:
        jax.profiler.trace = lambda d: plain(d, profiler_options=opts)
        try:
            with profile_to(out):
                time.sleep(seconds)
        finally:
            jax.profiler.trace = plain

    await asyncio.sleep(max(0.0, start_at - time.monotonic()))
    await asyncio.to_thread(capture)
    return out


def capture_seconds(traffic: dict, seconds: float) -> float:
    """How long a traced run's capture is: the window's last seconds."""
    return min(traffic.get("trace_seconds", 4.0), seconds / 2)


def backlog_over(ctx: Ctx, closed_at: float, capture_at: float) -> str:
    """What a traced run says, and exits on, when its driver closed the
    window before the capture began: the backlog was over, and a capture
    of an idle node must not become a line of anybody's record."""
    sized = {k: v for k, v in ctx.traffic.get("backlog", {}).items()
             if k != "note"}
    return (f"chipbench: {ctx.workload['name']}: the backlog was over at "
            f"window second {closed_at:.2f}, before the traced capture "
            f"begins at window second {capture_at:.2f} of {ctx.seconds:g}: "
            f"nothing of the program is left to capture.  The backlog is "
            f"sized by the 'backlog' section of chipbench/traffic/"
            f"{ctx.workload['traffic']}.json ({json.dumps(sized)}): resize it "
            f"from the rate this run reads")


def per_second_rates(t: list, weight: list, t0: float, t1: float) -> list:
    """Weights per whole second of ``[t0, t1)``."""
    n = int(t1 - t0)
    bins = [0.0] * n
    for ti, wi in zip(t, weight):
        k = int(ti - t0)
        if 0 <= ti - t0 and k < n:
            bins[k] += wi
    return bins


def quantile(values: list, q: float) -> float:
    s = sorted(values)
    if not s:
        raise ValueError("no samples")
    k = q * (len(s) - 1)
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


# ---- correct ----------------------------------------------------------------


@dataclass
class Offered:
    """What the traffic offered: the expectation the sink is held to."""

    expect: dict  # txid -> per-signature verdicts, by construction
    times: dict  # txid -> how many verdicts are due
    raw: dict  # txid -> raw tx (the reference's input)
    p2pk: dict  # the oracle's table


def reference_module(config: dict):
    """The plain reference a configuration is held to: the module of
    ``chipbench/`` its file names under ``reference`` (``reference`` where
    it names none).  Its ``check_job`` takes ``{"raw": [raw tx], "p2pk":
    the driver's prevout table, "checks": {...}}`` in a worker process and
    returns ``[(txid, per-signature verdicts)]``."""
    return importlib.import_module(
        "chipbench." + config.get("reference", "reference"))


async def run_reference(ctx: Ctx, offered: Offered, txids: list,
                        checks: dict | None = None) -> dict:
    """The configuration's plain reference over ``txids`` in the worker
    processes: txid -> per-signature verdicts.  ``checks`` weakens it (the
    controls)."""
    reference = reference_module(ctx.config)
    per = len(txids) // (4 * ctx.pool._processes) + 1
    jobs = [{"raw": [offered.raw[t] for t in txids[i:i + per]],
             "p2pk": offered.p2pk, "checks": checks or {}}
            for i in range(0, len(txids), per)]
    parts = await gather_jobs(ctx, reference.check_job, jobs)
    return dict(r for part in parts for r in part)


async def decide_correct(ctx: Ctx, offered: Offered, sink: Sink,
                         window: tuple, extra_checks: list) -> tuple:
    """-> (correct, attempted, failed, compared).  Every number compared
    is printed beside its limit; every limit is 0 (exact comparisons)."""
    got = collections.Counter()
    wrong = 0
    for txid, v in zip(sink.txids, sink.verdicts):
        got[txid] += 1
        want = offered.expect.get(txid)
        if want is None or v != want:
            wrong += 1
    missing = sum(max(0, n - got.get(t, 0)) for t, n in offered.times.items())
    extra = sum(max(0, n - offered.times.get(t, 0)) for t, n in got.items())
    attempted = sum(offered.times.values())
    checks = [
        ("verdicts_differing_from_construction", wrong),
        ("verdicts_missing", missing),
        ("verdicts_beyond_one_per_tx_offered", extra),
        ("verdicts_with_error", sink.errors),
        ("txs_shed", sink.shed),
    ] + list(extra_checks)

    # the plain reference over a seeded sample of what the window finished
    opened, closed = window
    in_window = sorted({tx for tx, t in zip(sink.txids, sink.t)
                        if opened <= t <= closed and tx in offered.raw})
    want_n = int(ctx.traffic.get("reference_sample_txs", 2000))
    sample = ctx.rng("reference").sample(in_window, min(want_n, len(in_window)))
    if not sample:
        checks.append(("reference_sample_empty", 1))
    t0 = time.monotonic()
    ref = await run_reference(ctx, offered, sample)
    seen = dict(zip(sink.txids, sink.verdicts))
    checks += [
        ("reference_vs_program", sum(ref[t] != seen.get(t) for t in sample)),
        ("reference_vs_construction",
         sum(ref[t] != offered.expect[t] for t in sample)),
    ]
    line("reference", sample_txs=len(sample),
         sample_sigs=sum(len(ref[t] or ()) for t in sample),
         invalid_in_sample=sum(not all(offered.expect[t]) for t in sample),
         seconds=round(time.monotonic() - t0, 3))
    for name, value in checks:
        line("compared", name=name, value=value, limit=0)
    failed = wrong + missing + extra + sink.errors + sink.shed
    correct = all(v == 0 for _, v in checks)
    return correct, attempted, failed, checks


# ---- per-layer metrics ------------------------------------------------------


@dataclass
class Reading:
    """What a per-layer reader may look at."""

    counters: dict  # counter deltas over the window
    window_s: float
    trace: dict | None  # tracered.reduce()'s output, traced runs only
    samples: dict  # driver's own samples (latencies, series)
    device: dict


def read_per_layer(ctx: Ctx, reading: Reading, root: str = ROOT) -> dict:
    out = {}
    cell = ctx.workload["name"]
    for entry in ctx.bench["per_layer"]:
        if "workloads" in entry and cell not in entry["workloads"]:
            continue
        spec = load_json(root, "chipbench", "metrics", entry["name"] + ".json")
        reader = importlib.import_module("chipbench.readers." + spec["reader"])
        value = reader.read(reading, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


@contextlib.contextmanager
def run_directory(ctx: Ctx):
    """Scratch for one run (store, trace), inside the checkout, removed at
    the end."""
    import shutil

    path = os.path.join(ROOT, ".chipbench_runs", f"{os.getpid()}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(os.path.join(path, "store"))
    ctx.run_dir = path
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


# ---- one run ----------------------------------------------------------------


def device_identity(ctx: Ctx) -> dict:
    """The device as JAX reports it; a run without the chips the cell asks
    for ends here, with no result."""
    import jax

    devs = jax.devices()
    ident = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if ctx.rehearsal is None:
        if ident["platform"] != "tpu" or ident["count"] != ctx.workload["chips"]:
            raise SystemExit(
                f"chipbench: {ctx.workload['name']} needs "
                f"{ctx.workload['chips']} TPU chip(s), JAX reports {ident}")
        known = load_json(HERE, "devices.json")
        if ident["kind"] not in known:
            raise SystemExit(f"chipbench: unknown device {ident['kind']!r}: "
                             "add it to chipbench/devices.json with its source")
    return ident


def peak_memory() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


async def run_cell(ctx: Ctx) -> dict:
    """Set-up, ramp, window, drain, check.  Returns the result object."""
    import gc

    from tpunode.metrics import metrics

    driver_mod = importlib.import_module(
        "chipbench.drivers." + ctx.traffic["driver"])
    with run_directory(ctx):
        start_pool(ctx)
        try:
            # the workers make the traffic while this process reaches the
            # chip and the engine warms up: set-up is the longest of the
            # three, not their sum
            driver = driver_mod.Driver(ctx)
            making = asyncio.ensure_future(driver.prepare())
            await asyncio.sleep(0)  # the jobs are with the pool
            try:
                ctx.device = device_identity(ctx)
                return await _run(ctx, driver, making, metrics, gc)
            finally:
                making.cancel()
                with contextlib.suppress(asyncio.CancelledError, Exception):
                    await making
        finally:
            ctx.pool.terminate()
            ctx.pool.join()


async def _run(ctx: Ctx, driver, making, metrics, gc) -> dict:
    require_native()
    remotes = driver.remotes()
    ports = [await r.start() for r in remotes]
    node, pub, store = make_node(ctx, driver.oracle, ports)
    if ctx.rehearsal is None:
        watch_compiles(ctx)
    sink = Sink()
    sink.listeners.append(driver.on_verdict)
    # a driver whose peers offer nothing until told may let the node dial
    # them while the engine warms up and the traffic is made
    early = getattr(driver, "CONNECT_EARLY", False)
    try:
        async with contextlib.AsyncExitStack() as stack:
            events = await stack.enter_async_context(pub.subscription())
            consumer = asyncio.ensure_future(consume(events, sink))
            try:
                if early:
                    await stack.enter_async_context(node)
                await making
                note(ctx, "traffic ready")
                warm = await await_engine(ctx, node)
                note(ctx, f"engine ready (waited {warm:.1f}s more)")
                if not early:
                    await stack.enter_async_context(node)
                base = metrics.snapshot()
                await driver.ramp(node, sink)
                gc.collect()
                gc.freeze()
                opened = mark(sink)
                note(ctx, "window opens")
                tracing = None
                if ctx.trace:
                    # the last seconds of the window: the profiler's own
                    # stop, which stalls the process, falls after it
                    span = capture_seconds(ctx.traffic, ctx.seconds)
                    capture_at = opened.t + ctx.seconds - span
                    tracing = asyncio.ensure_future(
                        traced(ctx, capture_at, span))
                while (time.monotonic() < opened.t + ctx.seconds
                       and not driver.closed_early(sink)):
                    await asyncio.sleep(0.02)
                closed = mark(sink)
                note(ctx, "window closes")
                if tracing is not None and closed.t < capture_at:
                    tracing.cancel()
                    with contextlib.suppress(asyncio.CancelledError):
                        await tracing
                    raise SystemExit(backlog_over(
                        ctx, closed.t - opened.t, capture_at - opened.t))
                trace_dir = await tracing if tracing is not None else None
                await driver.drain(node, sink)
                final = metrics.snapshot()
                memory = peak_memory() if ctx.rehearsal is None else 0
            finally:
                consumer.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await consumer
    finally:
        for r in remotes:
            await r.close()
        store.close()

    setup_s = opened.t - ctx.t_start
    e2e, samples = driver.end_to_end(sink, opened, closed)
    e2e["setup_s"] = setup_s
    in_window = [t for t in ctx.compiles if opened.t <= t <= closed.t]
    moved = [(n + "_moved", int(final.get(n, 0) - base.get(n, 0)))
             for n in GUARANTEE_COUNTERS
             if not (ctx.rehearsal is not None and n == "verify.cpu_items")]
    correct, attempted, failed, compared = await decide_correct(
        ctx, driver.offered, sink, (opened.t, closed.t),
        moved + driver.extra_checks()
        + [("compilations_inside_the_window", len(in_window))])

    window = {k: closed.counters.get(k, 0) - opened.counters.get(k, 0)
              for k in closed.counters}
    device = dict(ctx.device)
    reading = Reading(window, closed.t - opened.t, None, samples, device)
    if ctx.rehearsal is None:
        device["memory_peak_bytes"] = memory
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for m in ctx.bench["end_to_end"]}
    if trace_dir is not None and ctx.rehearsal is None:
        from chipbench import tracered

        reading.trace = tracered.reduce(
            trace_dir, spans=[k[5:-6] for k in final
                              if k.startswith("span.") and k.endswith(".count")])
        line("trace", window_s=reading.trace["window_s"],
             busy_s_by_chip=reading.trace["busy_s_by_chip"],
             kernels=reading.trace["kernels"], lines=reading.trace["lines"])
        device["busy_s"] = reading.trace["busy_s"]
        device["window_s"] = reading.trace["window_s"]
        result["breakdown"] = reading.trace["breakdown"]
    per_layer = read_per_layer(ctx, reading)
    if ctx.trace:
        result["metrics"] = per_layer
    else:
        line("per_layer_untraced", **{k: v["value"] for k, v in per_layer.items()})
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items()}
    line("run", workload=ctx.workload["name"], seed=ctx.seed,
         seconds=ctx.seconds, window_s=closed.t - opened.t, setup_s=setup_s,
         warmup_wait_s=warm, compiles=len(ctx.compiles),
         drain_verdicts=len(sink.t) - closed.n_verdicts)
    result["device"] = device
    if ctx.rehearsal is not None:
        result["rehearsal"] = True
    # each number compared beside its limit: the result's last key, and the
    # last lines of standard error
    result["compared"] = {name: {"value": value, "limit": 0}
                          for name, value in compared}
    for name, value in compared:
        print(f"compared {name} = {value} (limit 0)", file=sys.stderr)
    sys.stderr.flush()
    return result
