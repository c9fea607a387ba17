"""Initial block download of a chain that spends its own outputs:
``drivers/ibd_utxo.py`` — the held remote, the snapshot load, no prevout
callback, the set held to the plain reference after the drain — over
``gen_chain``'s blocks.

What differs from ``ibd_utxo`` is the chain and what is kept of it.  Six in
ten inputs spend an output the chain itself made — an earlier tx of the
same block, a block still inside the planner's lead, a block long
connected — so the node has to answer them from the block, from the outputs
of blocks it has parsed and not yet connected, and from entries of its set
that the run itself put there; the snapshot holds only the outpoints of
the other four.  The program is asked for that source by name before any
traffic is made: one that lacks it stops here with a line that says so.

A transaction's bytes are cut out of its block's frame when the reference
asks for them (``Raw``), and so is what the reference may know of a
prevout (``Prevouts``): for an input that spends the chain, the amount and
script of the parent's output as ``wirefmt.parse_tx`` reads them in the
parent's raw bytes — never anything the program said.
"""

from __future__ import annotations

import asyncio

import numpy as np

from chipbench import gen, gen_chain, harness
from chipbench import wirefmt as w
from chipbench.drivers import ibd, ibd_utxo

BLOCK_HEAD = w.HEADER_SIZE + 80  # the frame's envelope and the block's header

backlog = ibd.backlog  # the same mix a block: sized the same way


class Raw:
    """txid -> the tx's wire bytes, cut out of the served frame.  Every tx
    asked for is remembered: its prevouts are what ``Prevouts`` hands the
    reference."""

    def __init__(self, driver):
        self.d = driver
        self.asked: list = []

    def __contains__(self, txid) -> bool:
        return txid in self.d.block_of and txid not in self.d.coinbases

    def cut(self, txid) -> bytes:
        d = self.d
        b = d.block_of[txid]
        i = d.txids[b].index(txid)
        offs = d.offsets[b]
        frame = d.frames[d.hashes[b]]
        return frame[BLOCK_HEAD + offs[i]:BLOCK_HEAD + offs[i + 1]]

    def __getitem__(self, txid) -> bytes:
        raw = self.cut(txid)
        self.asked.append(raw)
        return raw

    def __iter__(self):
        return (t for ts in self.d.txids for t in ts)

    def __len__(self) -> int:
        return sum(len(ts) for ts in self.d.txids)


class Prevouts:
    """The reference's prevout table, made when it is pickled for a worker:
    outpoint -> ``(amount, script)`` for every input of the txs ``Raw`` was
    asked for that spends a tx of the chain (cut from the parent's raw
    bytes), outpoint -> script for a bare-P2PK entry of the snapshot.  What
    is in neither is the snapshot's and a function of its outpoint."""

    def __init__(self, driver):
        self.d = driver
        self.made = (0, {})  # txs asked for when it was made, the table

    def table(self) -> dict:
        d, out = self.d, {}
        if self.made[0] == len(d.offered.raw.asked):
            return self.made[1]  # a job's table is the next job's
        for raw in d.offered.raw.asked:
            (_, ins, _, _), _ = w.parse_tx(raw)
            for txid, vout, _, _ in ins:
                key = txid + vout.to_bytes(4, "little")
                if txid in d.block_of:
                    (_, _, outs, _), _ = w.parse_tx(d.offered.raw.cut(txid))
                    out[key] = outs[vout]
                elif key in d.values.p2pk:
                    out[key] = d.values.p2pk[key]
        self.made = (len(d.offered.raw.asked), out)
        return out

    def __reduce__(self):
        return dict, (self.table(),)


class Driver(ibd_utxo.Driver):
    def __init__(self, ctx):
        from tpunode import utxo

        if not hasattr(utxo, "InflightOutputs"):
            raise SystemExit(
                "chipbench: this program has no tpunode.utxo.InflightOutputs: "
                "it keeps no view of the outputs of blocks it has parsed and "
                "not yet connected, so an input that spends one is verified "
                "by nothing, and it cannot run " + ctx.workload["name"])
        super().__init__(ctx)
        self.offered = harness.Offered({}, {}, Raw(self), Prevouts(self))
        self.coinbases: set = set()
        self.txids: list = []  # per block, in block order (no coinbase)
        self.offsets: list = []  # per block: each tx's offsets in its body
        self.hashes: list = []
        self.frames: dict = {}
        self.got: dict = {}

    async def prepare(self) -> None:
        ctx, t = self.ctx, self.ctx.traffic
        parts = await harness.gather_jobs(
            ctx, gen_chain.strand_job,
            gen_chain.jobs_for(t, ctx.seed, self.n_blocks))
        bodies, self.txids, self.offsets = await asyncio.to_thread(
            gen_chain.weave, parts, self.n_blocks, self.per_block)
        spent = []
        for part in parts:
            self.offered.expect.update(zip(part["txids"], part["expect"]))
            self.values.p2pk.update(part["p2pk"])
            spent.append(part["snapshot"])
            for k, n in part["got"].items():
                self.got[k] = self.got.get(k, 0) + n
            part.clear()
        for b, (body, txids) in enumerate(zip(bodies, self.txids)):
            self.block_of.update(dict.fromkeys(txids, b))
            self.block_of[body[1]] = b
            self.coinbases.add(body[1])
            self.offered.expect[body[1]] = ()  # a coinbase signs nothing
        headers, self.hashes, self.frames = gen.chain_frames(
            ctx.config["network"], bodies)
        del bodies, parts
        self.remote.offer(headers, self.hashes, self.frames)
        self.totals = gen.totals(t["mix"], self.n_blocks * self.per_block)
        harness.line("traffic", blocks=self.n_blocks, **self.totals,
                     prevouts_from=self.got)
        # one snapshot entry for every outpoint the chain spends and did
        # not make
        spent = b"".join(spent)
        self.spendable = [spent[i:i + 36] for i in range(0, len(spent), 36)]
        self.n_filler = self.snapshot["entries"] - len(self.spendable)
        if self.n_filler < 0:
            raise SystemExit("chipbench: the chain spends more outputs than "
                             "the snapshot has entries")
        self.filler_seed = ctx.rng("snapshot").getrandbits(128)
        picks = ctx.rng("filler sample").sample(
            range(self.n_filler), min(ibd_utxo.SAMPLE, self.n_filler))
        self.filler_picks = np.array(sorted(picks), np.int64)
        harness.line("snapshot", entries=self.snapshot["entries"],
                     spendable=len(self.spendable), filler=self.n_filler)

    # -- after the window -----------------------------------------------------

    async def drain(self, node, sink) -> None:
        await super().drain(node, sink)
        view = node._inflight
        self.left_in_view = len(view) + view.blocks

    def _reference(self) -> tuple:
        ref, fillers = super()._reference()
        self.spent_absent = ref.spent_absent
        return ref, fillers

    def _compare(self, utxo, ref, fillers: list) -> int:
        """``ibd_utxo``'s three samples, with one difference: an output the
        chain made may have been spent by a later block, and is then held
        to be gone from both sets."""
        rng = self.ctx.rng("utxo sample")
        differs = 0
        for key in rng.sample(ref.spent, min(ibd_utxo.SAMPLE, len(ref.spent))):
            got = utxo.lookup(key[:32], int.from_bytes(key[32:], "little"))
            differs += got is not None or ref.lookup(key) is not None
        made = rng.sample(ref.created, min(ibd_utxo.SAMPLE, len(ref.created)))
        self.made_and_spent = sum(ref.lookup(key) is None for key in made)
        for key in made:
            got = utxo.lookup(key[:32], int.from_bytes(key[32:], "little"))
            differs += got != ref.lookup(key)
        for key in fillers:
            txid, vout = key[:32], int.from_bytes(key[32:], "little")
            want = gen.synth_amount(txid, vout), gen.synth_script(txid)
            differs += not utxo.lookup(txid, vout) == want == ref.lookup(key)
        return differs

    def extra_checks(self) -> list:
        # ibd_utxo's hold as they stand: a row the view answers never
        # reaches the set, and what the set is asked, it holds
        return super().extra_checks() + [
            ("spends_of_outputs_the_reference_set_lacked", self.spent_absent),
            ("outputs_left_in_the_view_after_the_last_connect",
             self.left_in_view),
            ("outputs_that_left_the_view_without_a_connect",
             self._moved("node.inflight_outputs_dropped")),
            ("resolves_that_gave_up_waiting",
             self._moved("node.resolve_gate_expired")),
        ]

    def end_to_end(self, sink, opened, closed) -> tuple:
        e2e, samples = super().end_to_end(sink, opened, closed)
        harness.line(
            "chain", prevouts_from=self.got,
            created_sample_spent_again=self.made_and_spent,
            inflight_rows=self._moved("node.resolve_inflight_rows"),
            inflight_hits=self._moved("node.resolve_inflight_hits"),
            set_hits=self._moved("utxo.lookup_hits"),
            resolve_rows=self._moved("node.resolve_rows"),
            gate_seconds=self._moved("span.node.resolve_gate.seconds"))
        return e2e, samples
