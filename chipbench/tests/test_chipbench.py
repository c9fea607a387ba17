import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import gen, harness, reference, secp, tracered
from chipbench.tests.rehearse import rehearse, tiny

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELLS = [wl["name"] for wl in BENCH["workloads"]]
MIX = harness.load_json(ROOT, "chipbench", "traffic", "ibd.json")["mix"]
RELAY = next(w["name"] for w in BENCH["workloads"] if w["traffic"] == "mempool")
DEVICE_ONLY = {"memory_peak_bytes", "busy_s", "window_s"}


def test_trace_reader_reduces_the_recorded_trace():
    red = tracered.reduce(os.path.join(ROOT, "benchmarks", "profiles", "r3"))
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["busy_s_by_chip"] == [red["busy_s"]]
    k = red["kernels"]["verify_blocked"]
    assert k["events"] == 6 and k["lanes"] == 3 and k["slots"] == 3 * 32768
    assert k["device_s"] == pytest.approx(0.268, abs=0.001)
    ops = red["breakdown"]["device_ops"]
    assert "verify_blocked" in ops[0][0] and len(ops) <= 10
    assert red["breakdown"]["idle_gaps"][0][0] == "none"


def test_a_run_cut_by_the_captures_edge_counts_neither_lane_nor_time():
    """Three whole runs of two kernel calls; a fourth run cut after its
    first call; a call whose run began before the capture."""
    op = "%verify_blocked.1 = (s32[1,4096]{1,0}) custom-call(s32[16,3,24,256])"
    runs = [(100, 200), (300, 400), (500, 600), (700, 760)]
    evs = [(50, 90, op)]
    for s, _ in runs[:3]:
        evs += [(s + 10, s + 40, op), (s + 50, s + 80, op), (s + 90, s + 95, "copy")]
    evs.append((710, 740, op))
    kernels = {"verify_blocked": {"device_s": 0.0, "events": 0, "lanes": 0,
                                  "slots": 0}}
    tracered.whole_runs(evs, runs, kernels)
    k = kernels["verify_blocked"]
    assert k["lanes"] == 3 and k["events"] == 6 and k["slots"] == 3 * 4096
    assert k["device_s"] == pytest.approx(6 * 30e-9)


def test_lane_width_from_either_name_form():
    assert tracered.lane_slots("__verify_blocked_jit.1_s32_1_4096_") == 4096
    assert tracered.lane_slots(
        "%verify_blocked.1 = (s32[1,32768]{1,0}) custom-call(s32[16,3,24,256])"
    ) == 32768


def _shape(out: dict) -> tuple:
    """What a job holds, apart from what the seed may change."""
    sizes = collections.Counter(len(r) // 8 for r in out["raw"])
    return (len(out["raw"]), sum(len(e) for e in out["expect"]),
            sum(not all(e) for e in out["expect"]),
            collections.Counter(out["adversarial"].values()), len(out["p2pk"]),
            sum(sizes.values()))


def test_stratified_generator_counts_do_not_depend_on_the_seed():
    mix = dict(MIX, adversarial_every=16)
    shapes = {seed: _shape(gen.gen_job(
        {"mix": mix, "seed": seed, "first_tx": 0, "count": 250, "total": 250}))
        for seed in [0, 1, 2, 3, 5, 8, 13, 2**31 + 7, 2**31 + 11, 99, 100, 101]}
    assert len(shapes) == 12
    first = next(iter(shapes.values()))
    assert all(s == first for s in shapes.values())
    tot = gen.totals(mix, 250)
    assert first[0] == tot["txs"] and first[1] == tot["sigs"]
    assert set(first[3]) == set(gen.ADVERSARIAL)
    # the seed does move the bytes, and which tx of a group is adversarial
    a = gen.gen_job({"mix": mix, "seed": 1, "first_tx": 0, "count": 64, "total": 64})
    b = gen.gen_job({"mix": mix, "seed": 2, "first_tx": 0, "count": 64, "total": 64})
    assert a["txids"] != b["txids"]
    assert a["adversarial"].keys() != b["adversarial"].keys()


def _totals_by_walking(mix: dict, count: int) -> dict:
    """``gen.totals`` as it was before PR 40: one step a tx (the reference
    for the closed form, which a 0.8M-tx chain's set-up wanted)."""
    tot = collections.Counter()
    for t in range(count):
        for kind in mix["pattern"][t % len(mix["pattern"])]:
            tot["inputs"] += 1
            tot["sigs"] += gen.SIGS[kind]
            tot["items"] += gen.ITEMS[kind]
            tot["in." + kind] += 1
    tot["txs"] = count
    for kind in gen.plan_adversarial(mix, 0, 0, count, count).values():
        tot["adv." + kind] += 1
    return dict(tot)


@pytest.mark.parametrize("every", [8, 16, 128])
def test_totals_in_closed_form_equal_totals_by_walking(every):
    mix = dict(MIX, adversarial_every=every)
    for count in (0, 1, 7, 8, 9, 63, 64, 100, 250, 1000, 4097, 66672):
        assert gen.totals(mix, count) == _totals_by_walking(mix, count), count


def test_signatures_verify_under_the_programs_python_oracle():
    """Valid by construction, and invalid exactly where the generator says:
    the program's Python reference extraction over its Python oracle, and
    the benchmark's own reference, both agree with the construction."""
    from tpunode.txverify import combine_verdicts, extract_sig_items
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.wire import Reader, Tx

    mix = dict(MIX, adversarial_every=8)
    out = gen.gen_job({"mix": mix, "seed": 2**31 + 5, "first_tx": 0, "count": 112, "total": 112})
    assert len(set(out["adversarial"].values())) == len(gen.ADVERSARIAL)
    oracle = gen.Oracle()
    oracle.p2pk = out["p2pk"]
    for raw, expect in zip(out["raw"], out["expect"]):
        tx = Tx.deserialize(Reader(raw))
        amounts, scripts = {}, {}
        for i, txin in enumerate(tx.inputs):
            amounts[i], scripts[i] = oracle(txin.prevout.txid, txin.prevout.index)
        items, st = extract_sig_items(tx, prevout_amounts=amounts, bch=True,
                                      prevout_scripts=scripts)
        assert st.unsupported == 0
        got = combine_verdicts(
            items, verify_batch_cpu([it.verify_item for it in items]))
        assert tuple(got) == expect
        assert reference.tx_verdicts(raw, oracle) == expect


class _Ev:
    def __init__(self, txid, verdicts):
        self.txid, self.verdicts = txid, verdicts
        self.valid, self.error = all(verdicts), None


def _decide(checks: secp.Checks) -> bool:
    """``correct`` for a verdict stream that a verifier with ``checks``
    would have produced over 256 txs holding every adversarial kind."""
    import asyncio
    import time

    bench, wl, cfg, tr = harness.load_cell(CELLS[0])
    tr = dict(tr, reference_sample_txs=40)
    ctx = harness.Ctx(wl, bench, cfg, tr, 5, 1.0, False, harness.Rehearsal(),
                      time.monotonic())
    mix = dict(MIX, adversarial_every=8)
    out = gen.gen_job({"mix": mix, "seed": 5, "first_tx": 0, "count": 256, "total": 256})
    oracle = gen.Oracle()
    oracle.p2pk = out["p2pk"]
    offered = harness.Offered(dict(zip(out["txids"], out["expect"])),
                              {t: 1 for t in out["txids"]},
                              dict(zip(out["txids"], out["raw"])), out["p2pk"])
    sink = harness.Sink()
    for txid, raw in zip(out["txids"], out["raw"]):
        sink.add(_Ev(txid, reference.tx_verdicts(raw, oracle, checks)), 0.5)
    harness.start_pool(ctx)
    try:
        correct, attempted, failed, _ = asyncio.run(harness.decide_correct(
            ctx, offered, sink, (0.0, 1.0), []))
    finally:
        ctx.pool.terminate()
        ctx.pool.join()
    assert attempted == 256 and (failed == 0) == correct
    return correct


@pytest.mark.parametrize("name,checks,want", [
    ("sound", secp.FULL, True),
    ("accepts_everything", secp.Checks(equation=False, scalar_range=False,
                                       on_curve=False), False),
    ("skips_the_range_checks", secp.Checks(scalar_range=False), False),
    ("skips_the_residue_check", secp.Checks(residue=False), False),
])
def test_correct_sees_a_weakened_verifier(name, checks, want):
    assert _decide(checks) is want


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_end_to_end_on_the_cpu(cell, capfd):
    """Every cell of BENCHMARK.json, from its own files alone: what makes
    it tiny is its traffic file's ``rehearsal`` section."""
    config = {}
    if json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                   cell.split(".")[0] + ".json")))["chips"] == 4:
        config = {"verify": {"mesh_hosts": 4}}
    res = rehearse(cell, config=config)
    assert list(res)[-1] == "compared"  # each number beside its limit, last
    assert all(v["limit"] == 0 for v in res["compared"].values())
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["rehearsal"] is True
    assert not DEVICE_ONLY & set(res["device"])
    assert res["device"]["platform"] == "cpu"
    wl = next(w for w in BENCH["workloads"] if w["name"] == cell)
    due = {m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    assert set(res["metrics"]) <= due and "setup_s" in res["metrics"]
    assert all(v["value"] > 0 for v in res["metrics"].values())
    out = capfd.readouterr().out
    for name in ("device.idle_share", "kernel.slots_per_busy_s",
                 "device.peak_hbm_mb"):
        assert name not in out  # no device metric from a CPU run
    assert wl["chips"] in (1, 4)


@pytest.mark.parametrize("n,want", [(999, None), (1001, 990.0)])
def test_the_tail_is_read_per_layer_from_the_drivers_samples(n, want):
    """``relay.verdict_p99_ms`` through its own metric file and reader; too
    few samples give nothing, and the harness leaves the metric out."""
    from chipbench.readers import sample_quantile

    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "relay.verdict_p99_ms")
    assert entry["workloads"] == [RELAY] and entry["moves"] == "verdict_p50_ms"
    assert not any(m["name"] == "verdict_p99_ms" for m in BENCH["end_to_end"])
    spec = harness.load_json(ROOT, "chipbench", "metrics", entry["name"] + ".json")
    reading = harness.Reading({}, 1.0, None,
                              {"verdict_ms": [float(i) for i in range(n)]}, {})
    assert sample_quantile.read(reading, **spec["args"]) == want


def test_a_broken_timed_path_reads_not_correct(monkeypatch):
    """The verifier under the engine says yes to everything: every other
    part of the run is sound, and ``correct`` comes out false."""
    from tpunode.verify.cpu_native import load_native_verifier

    monkeypatch.setattr(type(load_native_verifier()), "verify_raw",
                        lambda self, raw, nthreads=1: [True] * len(raw))
    res = rehearse(RELAY)
    assert res["correct"] is False and res["failed"] > 0


def test_a_run_off_its_rung_reads_not_correct():
    """Items served by the Python oracle instead of the configured rung:
    the verdicts are right and the run is still not correct."""
    res = rehearse(RELAY, seconds=4.0, config={"verify": {"backend": "oracle"}},
                   traffic=dict(tiny("mempool"), outstanding_per_peer=8))
    assert res["correct"] is False and res["failed"] == 0


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_a_new_cell_is_new_files_and_appended_entries(tmp_path):
    """A configuration, a mix and a per-layer metric with a reader of its
    own, added as new files; BENCHMARK.json only gains entries."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    cb = root / "chipbench"
    cfg = harness.load_json(ROOT, "chipbench", "configs", "bch-node.json")
    cfg["deployment"] = "fixture"
    (cb / "configs" / "fixture-node.json").write_text(json.dumps(cfg))
    mix = harness.load_json(ROOT, "chipbench", "traffic", "mempool.json")
    mix.update(peers=2, outstanding_per_peer=16)
    (cb / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (cb / "readers" / "verdict_count.py").write_text(
        "def read(reading, samples):\n"
        "    return float(len(reading.samples.get(samples, ())))\n")
    (cb / "metrics" / "relay.verdicts.json").write_text(json.dumps(
        {"layer": "fixture", "unit": "verdicts", "better": "higher",
         "source": "host_clock", "moves": "sigs_per_s",
         "reader": "verdict_count", "args": {"samples": "verdict_ms"}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "fixture-node", "source": "fixture",
                             "file": "chipbench/configs/fixture-node.json",
                             "reduced": [], "why": "fixture"})
    bench["workloads"].append({"name": "fixture-node.trickle",
                               "config": "fixture-node", "traffic": "trickle",
                               "chips": 1, "why": "fixture"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "verdict_p50_ms":
            m["workloads"].append("fixture-node.trickle")
    bench["per_layer"].append({"name": "relay.verdicts", "unit": "verdicts",
                               "better": "higher", "source": "host_clock",
                               "layer": "fixture", "moves": "sigs_per_s",
                               "workloads": ["fixture-node.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json; from chipbench.tests.rehearse import rehearse; "
            "print(json.dumps(rehearse('fixture-node.trickle', trace=True)))")
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join([str(root), ROOT])))
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["relay.verdicts"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())
