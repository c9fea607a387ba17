"""asyncsan static-analysis tests (ISSUE 3 tentpole).

Two contracts pinned here:

1. **The tree is clean**: the full analyzer over ``tpunode/`` + ``bench.py``
   reports ZERO findings — every rule shipped either holds across the
   codebase or carries an explicit suppression at its deliberate call
   site.  This is the lint gate: a new blocking call, dropped task
   handle, raw spawn or schema-violating name fails tier-1.
2. **Every rule fires**: a deliberately-seeded fixture per rule produces
   exactly one finding of exactly that rule, and the same fixture with a
   ``# asyncsan: disable=<rule>`` pragma on the flagged line lints clean.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tpunode.analysis import RULES, Analyzer, analyze_source
from tpunode.analysis.__main__ import default_paths, main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- the zero-findings gate --------------------------------------------------


def test_tree_is_clean():
    """ISSUE 3 acceptance (extended over benchmarks/ by ISSUE 8): the
    analyzer over the real tree finds nothing."""
    findings = Analyzer().check_paths(
        [
            os.path.join(REPO, "tpunode"),
            os.path.join(REPO, "bench.py"),
            os.path.join(REPO, "benchmarks"),
        ]
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_default_paths_cover_package_bench_and_benchmarks():
    paths = default_paths()
    assert paths[0].endswith("tpunode")
    assert paths[1].endswith("bench.py")
    assert paths[2].endswith("benchmarks")


# --- per-rule fixtures -------------------------------------------------------

# rule id -> source producing EXACTLY one finding of EXACTLY that rule.
FIXTURES = {
    "blocking-call": """\
import asyncio
import time

async def main():
    time.sleep(1)
""",
    "dropped-task": """\
import asyncio
from tpunode.actors import spawn_supervised

async def main(work):
    spawn_supervised(work())
""",
    "raw-spawn": """\
import asyncio

async def main(work):
    t = asyncio.create_task(work())
    await t
""",
    "lock-across-await": """\
import asyncio
import threading

_lock = threading.Lock()  # asyncsan: disable=raw-lock

async def main():
    with _lock:
        await asyncio.sleep(0)
""",
    "unawaited-coro": """\
async def work():
    return 1

async def main():
    work()
""",
    "cancel-swallow": """\
import asyncio

async def main(q):
    try:
        await q.get()
    except asyncio.CancelledError:
        pass
""",
    "thread-loop-affinity": """\
import threading

def pump(fut):
    fut.set_result(True)

def start(fut):
    threading.Thread(target=pump, args=(fut,)).start()
""",
    "pool-shutdown": """\
from concurrent.futures import ThreadPoolExecutor

def start():
    return ThreadPoolExecutor(max_workers=2)
""",
    "metric-name": """\
from tpunode.metrics import metrics

def record():
    metrics.inc("badName")
""",
    "event-name": """\
from tpunode.events import events

def record():
    events.emit("stats")
""",
    # schema-valid, registered layer, but absent from OBSERVABILITY.md's
    # inventory (ISSUE 16 doc-drift gate)
    "doc-drift": """\
from tpunode.metrics import metrics

def record():
    metrics.inc("node.fixture_undocumented")
""",
    # stale-doc (ISSUE 17) is doc-anchored, not source-anchored: it runs
    # once per sweep against OBSERVABILITY.md + the code corpus, so a
    # source fixture cannot drive it.  Dedicated tests below seed the
    # doc/corpus caches instead.
    "stale-doc": None,
    "raw-lock": """\
import threading

def make():
    return threading.Lock()
""",
    # env knob read nowhere documented in OBSERVABILITY.md's inventory
    "env-knob-doc": """\
import os

def knob():
    return os.environ.get("TPUNODE_FIXTURE_UNDOCUMENTED")
""",
    # dynamically-formatted label value with no bounded source (ISSUE
    # 19): the metric name itself is schema-valid and documented, so the
    # one finding is the cardinality hazard, not a naming complaint
    "label-cardinality": """\
from tpunode.metrics import metrics

def record(host_id):
    metrics.set_gauge(
        "sched.host_depth", 1.0, labels={"host": f"h{host_id}"}
    )
""",
}


def test_every_shipped_rule_has_a_fixture():
    assert set(FIXTURES) == set(RULES), (
        "rule set and fixture set diverged; add a fixture (and a fix or "
        "suppression policy) for every new rule"
    )


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_fires_exactly_once(rule_id):
    if FIXTURES[rule_id] is None:
        pytest.skip(f"{rule_id} is doc-anchored (dedicated tests below)")
    findings = analyze_source(FIXTURES[rule_id], path=f"<{rule_id}>")
    assert [f.rule for f in findings] == [rule_id], findings
    f = findings[0]
    assert f.line >= 1 and f.message


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_suppressed_on_flagged_line(rule_id):
    """The per-line pragma silences exactly the finding on its line."""
    if FIXTURES[rule_id] is None:
        pytest.skip(f"{rule_id} is doc-anchored (dedicated tests below)")
    src = FIXTURES[rule_id]
    line = analyze_source(src)[0].line
    lines = src.splitlines()
    lines[line - 1] += f"  # asyncsan: disable={rule_id}"
    assert analyze_source("\n".join(lines)) == []


def test_suppress_all_pragma():
    src = FIXTURES["blocking-call"]
    line = analyze_source(src)[0].line
    lines = src.splitlines()
    lines[line - 1] += "  # asyncsan: disable=all"
    assert analyze_source("\n".join(lines)) == []


def test_suppression_is_rule_specific():
    """A pragma for a DIFFERENT rule does not silence the finding."""
    src = FIXTURES["blocking-call"]
    line = analyze_source(src)[0].line
    lines = src.splitlines()
    lines[line - 1] += "  # asyncsan: disable=raw-spawn"
    assert [f.rule for f in analyze_source("\n".join(lines))] == [
        "blocking-call"
    ]


# --- rule-specific edges -----------------------------------------------------


def test_pool_shutdown_with_block_is_fine():
    """A pool created as a `with` target manages its own lifetime."""
    assert analyze_source(
        "from concurrent.futures import ThreadPoolExecutor\n"
        "def run(fn):\n"
        "    with ThreadPoolExecutor(2) as pool:\n"
        "        return pool.submit(fn)\n"
    ) == []


def test_pool_shutdown_teardown_elsewhere_is_fine():
    """A .shutdown() anywhere in the file is the shutdown path (the
    file-scope heuristic, like thread-loop-affinity) — the Node pattern:
    pool built in _start, shut down in __aexit__."""
    assert analyze_source(
        "from concurrent.futures import ThreadPoolExecutor\n"
        "class Owner:\n"
        "    def start(self):\n"
        "        self.pool = ThreadPoolExecutor(2)\n"
        "    def stop(self):\n"
        "        self.pool.shutdown(wait=False)\n"
    ) == []


def test_pool_shutdown_stored_then_with_is_fine():
    """A pool stored first and entered later via `with pool:` is
    context-managed — no finding (review edge)."""
    assert analyze_source(
        "from concurrent.futures import ThreadPoolExecutor\n"
        "def run(fn):\n"
        "    pool = ThreadPoolExecutor(2)\n"
        "    with pool:\n"
        "        return pool.submit(fn)\n"
    ) == []


def test_pool_shutdown_close_join_is_fine():
    """multiprocessing's canonical close()+join() graceful teardown is a
    shutdown path (review edge)."""
    assert analyze_source(
        "import multiprocessing\n"
        "def run():\n"
        "    p = multiprocessing.Pool(4)\n"
        "    p.close()\n"
        "    p.join()\n"
    ) == []


def test_pool_shutdown_flags_multiprocessing_too():
    findings = analyze_source(
        "import multiprocessing\n"
        "def start():\n"
        "    return multiprocessing.Pool(4)\n"
    )
    assert [f.rule for f in findings] == ["pool-shutdown"]


def test_pool_shutdown_unrelated_teardown_does_not_suppress():
    """Review edge: an unrelated file.close(), a `with lock:` block, and
    string .join(parts) plumbing must NOT count as the pool's shutdown
    path — the rule would be near-vacuous otherwise."""
    findings = analyze_source(
        "import threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "_lock = threading.Lock()  # asyncsan: disable=raw-lock\n"
        "def start(path, parts):\n"
        "    f = open(path)\n"
        "    f.close()\n"
        "    with _lock:\n"
        "        s = ','.join(parts)\n"
        "    return ThreadPoolExecutor(2)\n"
    )
    assert [f.rule for f in findings] == ["pool-shutdown"]


def test_blocking_call_resolves_import_aliases():
    src = "from time import sleep as snooze\nasync def f():\n    snooze(1)\n"
    assert [f.rule for f in analyze_source(src)] == ["blocking-call"]


def test_blocking_call_knows_durable_storage_syscalls():
    """ISSUE 9 satellite: os.fsync/os.replace (and friends) in async
    scope freeze the loop for an unbounded disk flush — the chain
    actor's durable commits must route through the group-commit writer
    thread instead."""
    src = """\
import os

async def f(fd, a, b):
    os.fsync(fd)
    os.fdatasync(fd)
    os.replace(a, b)
    os.rename(a, b)

def sync_is_fine(fd, a, b):
    os.fsync(fd)
    os.replace(a, b)
"""
    assert [f.rule for f in analyze_source(src)] == ["blocking-call"] * 4


def test_blocking_call_ignores_sync_and_threaded_scopes():
    src = """\
import asyncio
import time

def sync_path():
    time.sleep(1)

async def ok():
    await asyncio.to_thread(time.sleep, 1)
    f = lambda: time.sleep(1)
    return f
"""
    assert analyze_source(src) == []


def test_blocking_call_awaited_wait_is_fine():
    src = """\
import asyncio

async def f(ev, kick, remain):
    await ev.wait()
    await asyncio.wait_for(kick.wait(), timeout=remain)
    await asyncio.wait_for(asyncio.shield(ev.wait()), 5)
"""
    assert analyze_source(src) == []


def test_blocking_call_non_asyncio_wrapper_does_not_launder():
    """asyncio combinators pass awaitedness through to their arguments;
    an arbitrary wrapper does not — a blocker nested inside one still
    flags."""
    src = """\
async def f(g, h, p):
    await g(h(open(p)))
"""
    assert [f.rule for f in analyze_source(src)] == ["blocking-call"]


def test_unawaited_coro_deep_receiver_not_flagged():
    # `self._writer.write(...)`: an unrelated object sharing a method
    # name with a local async def must not be flagged
    src = """\
class C:
    async def write(self, data):
        pass

    def push(self, data):
        self._writer.write(data)
"""
    assert analyze_source(src) == []


def test_cancel_swallow_reraise_is_fine():
    src = """\
import asyncio

async def f(q):
    try:
        await q.get()
    except asyncio.CancelledError:
        raise
"""
    assert analyze_source(src) == []


def test_metric_name_covers_qualified_span_form():
    """`trace.span("...")` (module-qualified) is linted like bare
    `span("...")` — parity with the old regex lint's substring match."""
    src = """\
from tpunode import trace

def f():
    with trace.span("BadName"):
        pass
"""
    assert [f.rule for f in analyze_source(src)] == ["metric-name"]


def test_metric_name_covers_inc_batch_tuples():
    """The old regex lint in test_metrics never saw inc_batch literals."""
    src = """\
from tpunode.metrics import metrics

def f():
    metrics.inc_batch((("BadName", 1.0, None),))
"""
    assert [f.rule for f in analyze_source(src)] == ["metric-name"]


def test_event_name_has_no_grandfather():
    """ISSUE 3 satellite: the bare "stats" type (formerly grandfathered
    by test_metrics) now violates the schema; its replacement passes."""
    bad = "def f(log):\n    log.emit('stats')\n"
    good = "def f(log):\n    log.emit('node.stats')\n"
    assert [f.rule for f in analyze_source(bad)] == ["event-name"]
    assert analyze_source(good) == []


def test_name_layer_must_be_registered():
    """ISSUE 5 satellite: the `<layer>` half of a metric/event name must
    come from the registered set (rules.KNOWN_LAYERS) — a schema-shaped
    name on a typo'd layer ("mempol.") is a finding, and the new
    `mempool` layer is registered."""
    from tpunode.analysis.rules import KNOWN_LAYERS

    assert "mempool" in KNOWN_LAYERS
    bad_metric = (
        "from tpunode.metrics import metrics\n"
        "def f():\n    metrics.inc('mempol.dedup_hits')\n"
    )
    bad_event = "def f(log):\n    log.emit('mempol.orphan')\n"
    good = (
        "from tpunode.metrics import metrics\n"
        "def f(log):\n"
        "    metrics.inc('mempool.dedup_hits')\n"
        "    log.emit('mempool.orphan')\n"
    )
    (f,) = analyze_source(bad_metric)
    assert f.rule == "metric-name" and "unregistered layer" in f.message
    (f,) = analyze_source(bad_event)
    assert f.rule == "event-name" and "unregistered layer" in f.message
    assert analyze_source(good) == []


def test_inc_batch_layer_must_be_registered():
    src = (
        "from tpunode.metrics import metrics\n"
        "def f():\n"
        "    metrics.inc_batch((('mempol.x', 1.0, None),))\n"
    )
    (f,) = analyze_source(src)
    assert f.rule == "metric-name" and "unregistered layer" in f.message


def test_doc_drift_documented_names_are_clean():
    """Names with an OBSERVABILITY.md inventory row pass (metric, span
    and event forms alike)."""
    src = (
        "from tpunode.metrics import metrics\n"
        "from tpunode import trace\n"
        "def f(log):\n"
        "    metrics.inc('mempool.dedup_hits')\n"
        "    log.emit('node.stats')\n"
        "    with trace.span('verify.dispatch'):\n"
        "        pass\n"
    )
    assert analyze_source(src) == []


def test_doc_drift_covers_event_and_inc_batch_forms():
    """ISSUE 16: the rule lints the same call sites as
    metric-name/event-name — an undocumented (but schema-valid) event
    type and inc_batch tuple both flag as doc-drift."""
    src_event = "def f(log):\n    log.emit('node.fixture_undocumented')\n"
    src_batch = (
        "from tpunode.metrics import metrics\n"
        "def f():\n"
        "    metrics.inc_batch((('node.fixture_undocumented', 1.0, None),))\n"
    )
    for src in (src_event, src_batch):
        (f,) = analyze_source(src)
        assert f.rule == "doc-drift" and "OBSERVABILITY.md" in f.message


def test_doc_drift_never_double_reports_schema_violations():
    """A malformed or unregistered-layer name is metric-name/event-name's
    finding alone — one mistake, one finding."""
    src = (
        "from tpunode.metrics import metrics\n"
        "def f():\n    metrics.inc('mempol.dedup_hits')\n"
    )
    (f,) = analyze_source(src)
    assert f.rule == "metric-name"


def test_doc_drift_new_layers_registered():
    """ISSUE 16 registers the two new subsystems' layers."""
    from tpunode.analysis.rules import KNOWN_LAYERS

    assert "tsdb" in KNOWN_LAYERS and "blackbox" in KNOWN_LAYERS
    assert "slo" in KNOWN_LAYERS  # ISSUE 17


# --- stale-doc (ISSUE 17): doc-drift's reverse pass --------------------------

# The rule fires once per sweep, anchored on analysis/rules.py; findings
# carry the DOC's location.  These tests seed the module-level doc and
# corpus caches the rule reads, so no real files are touched.

_ANCHOR = "tpunode/analysis/rules.py"


def _seed_stale_doc(monkeypatch, doc, corpus):
    from tpunode.analysis import rules

    monkeypatch.setattr(rules, "_obs_doc_cache", [doc])
    monkeypatch.setattr(rules, "_corpus_cache", [corpus])


def _stale_findings(src=""):
    return [
        f
        for f in Analyzer(select=["stale-doc"]).check_source(
            src, path=_ANCHOR
        )
        if f.rule == "stale-doc"
    ]


def test_stale_doc_fires_on_removed_name(monkeypatch):
    doc = (
        "# OBSERVABILITY\n"
        "\n"
        "Current inventory by layer:\n"
        "\n"
        "* **`node.*`**: `node.fixture_gone` (counter).\n"
    )
    _seed_stale_doc(monkeypatch, doc, "metrics.inc('node.other')\n")
    (f,) = _stale_findings()
    assert f.rule == "stale-doc"
    assert "node.fixture_gone" in f.message
    assert f.path.endswith("OBSERVABILITY.md") and f.line == 5


def test_stale_doc_clean_when_name_ships(monkeypatch):
    doc = (
        "Current inventory by layer:\n"
        "* **`node.*`**: `node.fixture_alive{peer=}` (labeled counter).\n"
    )
    _seed_stale_doc(
        monkeypatch, doc, "metrics.inc('node.fixture_alive', labels=l)\n"
    )
    assert _stale_findings() == []


def test_stale_doc_covers_events_table_and_span_rows(monkeypatch):
    """Pipe-table rows with a backticked first cell are inventory too,
    and `span.<layer>.<name>` rows match the bare span(...) literal."""
    doc = (
        "| type | fields |\n"
        "|---|---|\n"
        "| `node.fixture_event` | `x` |\n"
        "\n"
        "Current inventory by layer:\n"
        "* `span.node.fixture_phase` (histogram).\n"
    )
    _seed_stale_doc(
        monkeypatch, doc,
        "log.emit('node.fixture_event')\nspan('node.fixture_phase')\n",
    )
    assert _stale_findings() == []
    _seed_stale_doc(monkeypatch, doc, "nothing_here = 1\n")
    assert {
        f.message.split("'")[1] for f in _stale_findings()
    } == {"node.fixture_event", "span.node.fixture_phase"}


def test_stale_doc_suppressible_per_doc_row(monkeypatch):
    doc = (
        "Current inventory by layer:\n"
        "* `node.fixture_dynamic` (built at runtime) "
        "<!-- # asyncsan: disable=stale-doc -->\n"
    )
    _seed_stale_doc(monkeypatch, doc, "nothing_here = 1\n")
    assert _stale_findings() == []


def test_stale_doc_only_fires_on_its_anchor_file(monkeypatch):
    """One sweep, one pass: the rule is anchored on analysis/rules.py and
    stays silent for every other analyzed file."""
    doc = (
        "Current inventory by layer:\n"
        "* `node.fixture_gone` (counter).\n"
    )
    _seed_stale_doc(monkeypatch, doc, "nothing_here = 1\n")
    out = Analyzer(select=["stale-doc"]).check_source("", path="other.py")
    assert out == []


def test_stale_doc_missing_doc_disables(monkeypatch):
    _seed_stale_doc(monkeypatch, None, "nothing_here = 1\n")
    assert _stale_findings() == []


def test_syntax_error_is_a_finding_not_a_crash():
    out = analyze_source("def broken(:\n")
    assert [f.rule for f in out] == ["syntax-error"]


def test_rule_subset_selection():
    src = FIXTURES["blocking-call"] + FIXTURES["unawaited-coro"]
    only = Analyzer(select=["unawaited-coro"]).check_source(src)
    assert {f.rule for f in only} == {"unawaited-coro"}
    with pytest.raises(ValueError):
        Analyzer(select=["no-such-rule"])


def test_registry_catalog_complete():
    for r in RULES.values():
        assert r.id and r.summary and callable(r.check)


# --- CLI ---------------------------------------------------------------------


def test_cli_inprocess_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["blocking-call"], encoding="utf-8")
    assert cli_main([str(bad)]) == 1
    text = capsys.readouterr().out
    assert "blocking-call" in text and "bad.py" in text

    assert cli_main(["--json", str(bad)]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["findings"][0]["rule"] == "blocking-call"
    assert data["findings"][0]["line"] == 5

    good = tmp_path / "good.py"
    good.write_text("x = 1\n", encoding="utf-8")
    assert cli_main([str(good)]) == 0

    assert cli_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in ("raw-spawn", "raw-lock", "env-knob-doc"):
        assert rid in listed
    assert cli_main(["--rules", "bogus", str(good)]) == 2
    assert cli_main([str(tmp_path / "missing.py")]) == 2


def test_cli_subprocess_tree_is_clean():
    """ISSUE 3 acceptance, verbatim: ``python -m tpunode.analysis
    tpunode/`` exits 0 with zero findings on the final tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "tpunode.analysis", "--json", "tpunode"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["findings"] == []


# --- raw-lock / env-knob-doc (ISSUE 18) -------------------------------------


def test_raw_lock_flags_aliases_and_dynamic_import():
    src = (
        "from threading import Lock as L\n"
        "a = L()\n"
        'b = __import__("threading").RLock()\n'
    )
    findings = analyze_source(src)
    assert [f.rule for f in findings] == ["raw-lock", "raw-lock"]
    assert [f.line for f in findings] == [2, 3]


def test_raw_lock_ignores_asyncio_and_registry_locks():
    src = (
        "import asyncio\n"
        "from tpunode import threadsan\n"
        "a = asyncio.Lock()\n"
        'b = threadsan.lock("node.fixture")\n'
        'c = threadsan.rlock("node.fixture_r")\n'
    )
    assert analyze_source(src) == []


def test_raw_lock_exempts_threadsan_itself():
    src = "import threading\n_meta = threading.Lock()\n"
    assert (
        Analyzer(select=["raw-lock"]).check_source(
            src, path="tpunode/threadsan.py"
        )
        == []
    )
    assert [
        f.rule
        for f in Analyzer(select=["raw-lock"]).check_source(
            src, path="tpunode/store.py"
        )
    ] == ["raw-lock"]


def test_env_knob_doc_containment(monkeypatch):
    _seed_stale_doc(monkeypatch, "| `TPUNODE_DOCUMENTED=1` | a knob |", "")
    src = (
        "import os\n"
        'a = os.environ.get("TPUNODE_DOCUMENTED")\n'
        'b = os.environ.get("TPUNODE_NOT_DOCUMENTED")\n'
        'c = "TPUNODE_" + a\n'  # prefix-building: not a knob literal
    )
    findings = [
        f
        for f in Analyzer(select=["env-knob-doc"]).check_source(src)
        if f.rule == "env-knob-doc"
    ]
    assert [f.line for f in findings] == [3]
    assert "TPUNODE_NOT_DOCUMENTED" in findings[0].message


def test_env_knob_doc_ignores_docstrings(monkeypatch):
    _seed_stale_doc(monkeypatch, "nothing documented", "")
    src = '"""Module mentioning TPUNODE_SOMETHING in prose."""\nx = 1\n'
    assert Analyzer(select=["env-knob-doc"]).check_source(src) == []
