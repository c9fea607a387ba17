"""Initial block download over the node's own UTXO set: ``drivers/ibd.py``
with no prevout callback.  The node is given a snapshot before the first
block is offered — one entry for every outpoint the chain spends, with the
amount and script ``gen.Oracle`` would have answered, and seeded filler of
the same shape up to the configuration's ``node.utxo_snapshot.entries`` —
and answers every prevout from it; the chain's spends are deletes that
hit.  After the drain the node's set is held to the plain reference's
(``reference_utxo.py``): the live count, and three seeded samples.

Everything else is the ``ibd`` driver's, by import: the chain, the
planner's pulls, the window, the statistic, its two checks.
"""

from __future__ import annotations

import asyncio
import copy
import resource
import time

import numpy as np

from chipbench import gen, harness, reference_utxo
from chipbench import wirefmt as w
from chipbench.drivers import ibd
from chipbench.peers import Remote

CHUNK = 1 << 18  # snapshot entries a batch: one append, one hold of the lock
SAMPLE = 2000  # outpoints of each kind held to the reference


def _record(width: int) -> np.dtype:
    """A delta blob's put of one output whose script is ``width`` bytes
    (``UtxoStore.load_snapshot``'s format)."""
    return np.dtype([("op", "u1"), ("klen", "<u4"), ("vlen", "<u4"),
                     ("o", "u1"), ("outpoint", "u1", (36,)),
                     ("value", "u1", (8 + width,))])


def _values(outpoints: np.ndarray, scripts: np.ndarray) -> np.ndarray:
    """``gen.synth_amount`` of every row as 8 little-endian bytes, then the
    row's script: the store's value for an output."""
    n = len(outpoints)
    low = np.zeros((n, 8), np.uint8)
    low[:, :6] = outpoints[:, :6]
    vouts = np.ascontiguousarray(outpoints[:, 32:]).view("<u4").reshape(n)
    amounts = (low.view("<u8").reshape(n) ^ vouts) % 5_000_000 + 10_000
    out = np.empty((n, 8 + scripts.shape[1]), np.uint8)
    out[:, :8] = amounts.astype("<u8").view(np.uint8).reshape(n, 8)
    out[:, 8:] = scripts
    return out


def _synth_scripts(outpoints: np.ndarray) -> np.ndarray:
    """``gen.synth_script`` of every row."""
    out = np.empty((len(outpoints), 25), np.uint8)
    out[:, :3] = (0x76, 0xA9, 0x14)
    out[:, 3:23] = outpoints[:, :20]
    out[:, 23:] = (0x88, 0xAC)
    return out


def _rows(table: np.ndarray) -> list:
    return np.ascontiguousarray(table).reshape(-1).view(
        f"V{table.shape[1]}").tolist()


class HeldRemote(Remote):
    """A peer that keeps the chain it was given to itself until told: the
    node may dial it, and learns of the chain by a ``headers`` announcement
    when it is released."""

    def __init__(self, net: dict):
        super().__init__(net, on_ready=self._ready)
        self.held = None
        self.ready: list = []  # writers whose handshake is through

    async def _ready(self, writer) -> None:
        self.ready.append(writer)

    def offer(self, headers: list, hashes: list, blocks: dict) -> None:
        self.held = (headers, hashes, blocks)

    def release(self) -> None:
        super().offer(*self.held)
        for writer in self.ready:
            writer.write(self._headers_reply([]))


backlog = ibd.backlog  # the same chain, sized the same way


class Driver(ibd.Driver):
    def __init__(self, ctx):
        from tpunode.metrics import metrics
        from tpunode.utxo import UtxoStore

        if not hasattr(UtxoStore, "load_snapshot"):
            raise SystemExit(
                "chipbench: this program's UtxoStore has no load_snapshot: "
                "it cannot be given a UTXO set, so it cannot run "
                + ctx.workload["name"])
        super().__init__(ctx)
        # the harness gives the node what it finds here: no callback.  The
        # generator's table (the reference's, and the snapshot's values)
        # stays with the driver
        self.values, self.oracle = self.oracle, None
        self.remote = HeldRemote(ctx.config["network"])
        self.snapshot = ctx.config["node"]["utxo_snapshot"]
        self.counters = metrics
        self.base = metrics.snapshot()
        self.load_s = 0.0

    async def prepare(self) -> None:
        # ibd's prepare fills the P2PK table through ``self.oracle``, which
        # here has to read None: run it on a twin that shares every
        # container with this driver and holds the table there
        twin = copy.copy(self)
        twin.oracle = self.values
        await ibd.Driver.prepare(twin)
        self.totals = twin.totals
        self.frames = self.remote.held[2]
        # one snapshot entry for every outpoint the chain spends
        spent = []
        for raw in self.offered.raw.values():
            (_, ins, _, _), _ = w.parse_tx(raw)
            spent += [txid + vout.to_bytes(4, "little")
                      for txid, vout, _, _ in ins]
        self.spendable = spent
        self.n_filler = self.snapshot["entries"] - len(spent)
        if self.n_filler < 0:
            raise SystemExit("chipbench: the chain spends more outputs than "
                             "the snapshot has entries")
        self.filler_seed = self.ctx.rng("snapshot").getrandbits(128)
        picks = self.ctx.rng("filler sample").sample(
            range(self.n_filler), min(SAMPLE, self.n_filler))
        self.filler_picks = np.array(sorted(picks), np.int64)
        harness.line("snapshot", entries=self.snapshot["entries"],
                     spendable=len(spent), filler=self.n_filler)

    # -- the snapshot ---------------------------------------------------------

    def snapshot_columns(self):
        """The snapshot in columns, a batch at a time: ``(outpoints (n, 36),
        values (n, 8 + script width))`` uint8 tables.  The chain's own
        outpoints first (bare P2PK ones carry the generator's script, the
        rest the synthetic one), then the filler, from the seed."""
        table = np.frombuffer(b"".join(self.spendable), np.uint8).reshape(-1, 36)
        p2pk = self.values.p2pk
        bare = np.array([k in p2pk for k in self.spendable], bool)
        if bare.any():
            scripts = np.frombuffer(b"".join(
                p2pk[k] for k in self.spendable if k in p2pk), np.uint8)
            yield table[bare], _values(table[bare], scripts.reshape(-1, 35))
        rest = table[~bare]
        for lo in range(0, len(rest), CHUNK):
            part = rest[lo:lo + CHUNK]
            yield part, _values(part, _synth_scripts(part))
        rng = np.random.Generator(np.random.PCG64(self.filler_seed))
        for lo in range(0, self.n_filler, CHUNK):
            n = min(CHUNK, self.n_filler - lo)
            part = np.empty((n, 36), np.uint8)
            part[:, :32] = rng.integers(0, 256, (n, 32), np.uint8)
            part[:, 32:] = 0
            part[:, 32] = rng.integers(0, 4, n, np.uint8)  # vout 0-3
            yield part, _values(part, _synth_scripts(part))

    def snapshot_batches(self):
        """The same as delta blobs of puts, for ``load_snapshot``."""
        for outpoints, values in self.snapshot_columns():
            rec = np.empty(len(outpoints), _record(values.shape[1] - 8))
            rec["op"], rec["klen"], rec["o"] = 1, 37, ord("o")
            rec["vlen"] = values.shape[1]
            rec["outpoint"], rec["value"] = outpoints, values
            yield rec.tobytes()

    async def ramp(self, node, sink) -> None:
        net = self.ctx.config["network"]
        t0 = time.monotonic()
        n = await asyncio.to_thread(
            node.utxo.load_snapshot, self.snapshot["height"],
            w.sha256d(w.genesis_header(net)), self.snapshot_batches())
        self.load_s = time.monotonic() - t0
        harness.note(self.ctx, f"snapshot of {n} entries loaded "
                               f"in {self.load_s:.1f}s")
        if n != self.snapshot["entries"]:
            raise SystemExit(f"chipbench: the node loaded {n} entries of "
                             f"{self.snapshot['entries']}")
        self.remote.release()
        await super().ramp(node, sink)

    # -- after the window -----------------------------------------------------

    async def drain(self, node, sink) -> None:
        self.rss_mb = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1024  # at the window's close
        self.entries_at_close = node.utxo.entries
        await super().drain(node, sink)
        self.callback_left_in = node.cfg.prevout_lookup is not None
        t0 = time.monotonic()
        ref, fillers = await asyncio.to_thread(self._reference)
        self.entries_differ = abs(node.utxo.entries - len(ref.set))
        self.sample_differs = self._compare(node.utxo, ref, fillers)
        harness.line("utxo_reference", entries=len(ref.set),
                     node_entries=node.utxo.entries, spent=len(ref.spent),
                     created=len(ref.created), spent_absent=ref.spent_absent,
                     seconds=round(time.monotonic() - t0, 3))

    def _reference(self) -> tuple:
        """The plain reference after the served blocks, and the sampled
        filler outpoints."""
        ref = reference_utxo.UtxoSet()
        fillers, seen = [], -len(self.spendable)
        for outpoints, values in self.snapshot_columns():
            rows = _rows(outpoints)
            ref.seed(rows, _rows(values))
            lo = np.searchsorted(self.filler_picks, seen)
            hi = np.searchsorted(self.filler_picks, seen + len(rows))
            fillers += [rows[i - seen] for i in self.filler_picks[lo:hi]]
            seen += len(rows)
        heights = sorted({self.remote.index[h] for h in self.served})
        for i in heights:
            ref.apply_block(self.frames[self.remote.hashes[i]][w.HEADER_SIZE:])
        return ref, fillers

    def _compare(self, utxo, ref, fillers: list) -> int:
        """Three seeded samples of outpoints through the node's own lookup:
        spent ones must be gone, created ones there as their tx made them,
        untouched filler there as the generator's functions give it."""
        rng = self.ctx.rng("utxo sample")
        differs = 0
        for key in rng.sample(ref.spent, min(SAMPLE, len(ref.spent))):
            got = utxo.lookup(key[:32], int.from_bytes(key[32:], "little"))
            differs += got is not None or ref.lookup(key) is not None
        for key in rng.sample(ref.created, min(SAMPLE, len(ref.created))):
            got = utxo.lookup(key[:32], int.from_bytes(key[32:], "little"))
            differs += got is None or got != ref.lookup(key)
        for key in fillers:
            txid, vout = key[:32], int.from_bytes(key[32:], "little")
            want = gen.synth_amount(txid, vout), gen.synth_script(txid)
            differs += not utxo.lookup(txid, vout) == want == ref.lookup(key)
        return differs

    def _moved(self, name: str) -> int:
        return int(self.counters.get(name) - self.base.get(name, 0))

    def extra_checks(self) -> list:
        return super().extra_checks() + [
            # a callback left configured breaks the guarantee whether or
            # not a row ever reached it
            ("prevout_callback_calls",
             self._moved("node.resolve_oracle_calls") + self.callback_left_in),
            ("rows_no_source_answered", self._moved("node.resolve_missing")),
            ("utxo_lookup_misses", self._moved("utxo.lookup_rows")
             - self._moved("utxo.lookup_hits")),
            ("utxo_entries_differ", self.entries_differ),
            ("utxo_sample_differs", self.sample_differs),
        ]

    def end_to_end(self, sink, opened, closed) -> tuple:
        e2e, samples = super().end_to_end(sink, opened, closed)
        samples.update(snapshot_load_s=[self.load_s],
                       utxo_entries=[float(self.entries_at_close)],
                       rss_mb=[self.rss_mb])
        return e2e, samples
