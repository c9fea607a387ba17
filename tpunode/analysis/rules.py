"""asyncsan rule set: the hazard classes this codebase has actually hit.

Every rule id doubles as its suppression token
(``# asyncsan: disable=<id>``); ANALYSIS.md is the user-facing catalog.
The selection is deliberately grounded in this node's architecture —
actor mailboxes drained by linked loops on ONE event loop, a verify
engine whose dispatch runs in a worker thread, and a telemetry layer with
a pinned ``<layer>.<name>`` naming schema.
"""

from __future__ import annotations

import ast
import os
import re

from .core import FileContext, Finding, NAME_SCHEMA_RE, rule

# --- blocking-call -----------------------------------------------------------

# Qualified call names that block the calling thread.  Inside an
# ``async def`` these freeze the event loop: every mailbox, timer, peer
# session and watchdog shares that one thread (actors.py's substrate).
_BLOCKING_CALLS = {
    "time.sleep",
    "os.system",
    "os.popen",
    "os.wait",
    "os.waitpid",
    # durable-storage syscalls (ISSUE 9): an fsync is milliseconds on a
    # good day and unbounded on a bad one, and a cross-filesystem replace
    # degrades to a copy — the chain actor's durable commits route them
    # through LogKV's group-commit writer thread instead
    "os.fsync",
    "os.fdatasync",
    "os.replace",
    "os.rename",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
    "subprocess.Popen",
    "socket.create_connection",
    "socket.getaddrinfo",
    "socket.gethostbyname",
    "socket.gethostbyaddr",
    "urllib.request.urlopen",
    "requests.get",
    "requests.post",
    "requests.put",
    "requests.head",
    "requests.delete",
    "requests.request",
    "open",
    "input",
}

# Methods that block regardless of receiver when NOT awaited:
# ``fut.result()`` (concurrent.futures) and jax's ``block_until_ready()``
# synchronize on work that may never finish while the loop is frozen.
_BLOCKING_METHODS = {"result", "block_until_ready"}

# Methods that block only in their no-positional-arg form — distinguishes
# ``thread.join()`` / ``event.wait()`` / ``lock.acquire()`` from
# ``sep.join(parts)`` (always one positional arg).  A NON-awaited bare
# ``.wait()``/``.acquire()`` inside ``async def`` is either a threading
# primitive (blocks the loop) or a missed ``await`` on the asyncio one —
# a hazard either way.
_BLOCKING_METHODS_NOARG = {"join", "wait", "acquire"}


@rule(
    "blocking-call",
    "blocking call inside `async def` freezes the event loop "
    "(wrap in asyncio.to_thread, or use the async equivalent)",
)
def _blocking_call(ctx: FileContext) -> None:
    for call, awaited in ctx.async_scope_calls():
        if awaited:
            continue
        qual = ctx.resolve(call.func)
        if qual in _BLOCKING_CALLS:
            ctx.report(
                "blocking-call", call,
                f"blocking call {qual}() inside async def",
            )
            continue
        if isinstance(call.func, ast.Attribute):
            attr = call.func.attr
            if attr in _BLOCKING_METHODS or (
                attr in _BLOCKING_METHODS_NOARG and not call.args
            ):
                ctx.report(
                    "blocking-call", call,
                    f"potentially blocking .{attr}() inside async def "
                    "(not awaited)",
                )


# --- dropped-task ------------------------------------------------------------

_SPAWN_QUALS = {"asyncio.create_task", "asyncio.ensure_future"}
_SPAWN_ATTRS = {"create_task", "ensure_future"}
_SPAWN_NAMES = {"spawn_supervised"}


def _is_spawn(ctx: FileContext, call: ast.Call) -> bool:
    qual = ctx.resolve(call.func)
    if qual in _SPAWN_QUALS:
        return True
    if qual is not None and qual.split(".")[-1] in _SPAWN_NAMES:
        return True
    return (
        isinstance(call.func, ast.Attribute) and call.func.attr in _SPAWN_ATTRS
    )


@rule(
    "dropped-task",
    "task handle discarded at spawn: the task can be garbage-collected "
    "mid-flight and its exception is never observed (keep the handle, or "
    "hand it to a supervisor)",
)
def _dropped_task(ctx: FileContext) -> None:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Expr):
            continue
        call = node.value
        if isinstance(call, ast.Call) and _is_spawn(ctx, call):
            name = ctx.resolve(call.func) or ast.unparse(call.func)
            ctx.report(
                "dropped-task", node,
                f"fire-and-forget {name}(...): task handle dropped",
            )


# --- raw-spawn ---------------------------------------------------------------


@rule(
    "raw-spawn",
    "direct create_task/ensure_future bypasses the supervision registry "
    "(use actors.spawn_supervised so leaks are reported at shutdown)",
)
def _raw_spawn(ctx: FileContext) -> None:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        qual = ctx.resolve(node.func)
        is_raw = qual in _SPAWN_QUALS or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SPAWN_ATTRS
        )
        if is_raw:
            name = qual or f".{node.func.attr}"  # type: ignore[union-attr]
            ctx.report(
                "raw-spawn", node,
                f"{name}(...) outside the supervision registry: route "
                "through actors.spawn_supervised",
            )


# --- lock-across-await -------------------------------------------------------


def _mentions_lock(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "lock" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "lock" in sub.attr.lower():
            return True
    return False


@rule(
    "lock-across-await",
    "synchronous lock held across `await`: other tasks (and the metrics/"
    "event emitters on worker threads) deadlock against the frozen holder",
)
def _lock_across_await(ctx: FileContext) -> None:
    # Only sync ``with`` blocks: ``async with asyncio.Lock()`` awaits by
    # design.  A threading/`_lock`-style guard whose body awaits keeps the
    # lock held while OTHER code runs on this thread — the cross-thread
    # emitters then block a worker thread against a loop that may be
    # awaiting that very worker (the verify-engine dispatch boundary).
    def walk(node: ast.AST, in_async: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.AsyncFunctionDef):
                walk(child, True)
                continue
            if isinstance(child, (ast.FunctionDef, ast.Lambda)):
                walk(child, False)
                continue
            if (
                in_async
                and isinstance(child, ast.With)
                and any(_mentions_lock(item.context_expr) for item in child.items)
                and any(isinstance(n, ast.Await) for n in ast.walk(child))
            ):
                ctx.report(
                    "lock-across-await", child,
                    "sync lock held across await inside async def",
                )
            walk(child, in_async)

    walk(ctx.tree, False)


# --- unawaited-coro ----------------------------------------------------------


@rule(
    "unawaited-coro",
    "call to a locally-defined `async def` whose coroutine is discarded: "
    "the body never runs (RuntimeWarning at GC, silently dropped work)",
)
def _unawaited_coro(ctx: FileContext) -> None:
    names = ctx.async_defs
    if not names:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Expr):
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        called = None
        if isinstance(func, ast.Name) and func.id in names:
            called = func.id
        elif (
            isinstance(func, ast.Attribute)
            and func.attr in names
            # only `self.<name>` receivers: a deeper chain (e.g.
            # `self._writer.write`) usually reaches an unrelated object
            # that merely shares a method name with a local async def
            and isinstance(func.value, ast.Name)
            and func.value.id == "self"
        ):
            called = func.attr
        if called is not None:
            ctx.report(
                "unawaited-coro", node,
                f"coroutine {called}(...) is never awaited",
            )


# --- cancel-swallow ----------------------------------------------------------

_CANCEL_NAMES = {
    "asyncio.CancelledError",
    "CancelledError",
    "concurrent.futures.CancelledError",
    "BaseException",
}


def _catches_cancelled(ctx: FileContext, handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:  # bare except
        return True
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    for node in types:
        qual = ctx.resolve(node)
        if qual in _CANCEL_NAMES or (
            qual is not None and qual.split(".")[-1] == "CancelledError"
        ):
            return True
    return False


@rule(
    "cancel-swallow",
    "except clause swallows CancelledError: shutdown cancellation never "
    "propagates and the task loops forever (re-raise it)",
)
def _cancel_swallow(ctx: FileContext) -> None:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _catches_cancelled(ctx, node):
            continue
        if any(isinstance(n, ast.Raise) for n in ast.walk(node)):
            continue
        what = "bare except" if node.type is None else (
            ctx.resolve(node.type)
            if not isinstance(node.type, ast.Tuple)
            else "except (...)"
        )
        ctx.report(
            "cancel-swallow", node,
            f"{what} catches CancelledError without re-raising",
        )


# --- thread-loop-affinity ----------------------------------------------------

# Loop-affine calls: mutating these from a non-loop thread corrupts
# asyncio internals or races the consumer (asyncio.Queue.put_nowait and
# Mailbox.send are NOT thread-safe).  The verify-engine dispatch-worker
# boundary is exactly this seam: results cross back via the future the
# *loop* resolves, never via direct mutation from the worker.
_LOOP_AFFINE_ATTRS = {
    "set_result",
    "set_exception",
    "call_soon",
    "call_later",
    "call_at",
    "create_task",
    "ensure_future",
    "put_nowait",
    "send",
}


def _thread_target_names(ctx: FileContext) -> set[str]:
    """Names of local defs handed to worker threads: Thread(target=f),
    asyncio.to_thread(f, ...), loop.run_in_executor(None, f, ...)."""
    targets: set[str] = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        qual = ctx.resolve(node.func)
        is_thread = qual == "threading.Thread" or (
            isinstance(node.func, ast.Attribute) and node.func.attr == "Thread"
        )
        if is_thread:
            for kw in node.keywords:
                if kw.arg == "target" and isinstance(kw.value, ast.Name):
                    targets.add(kw.value.id)
            continue
        if qual == "asyncio.to_thread" and node.args:
            if isinstance(node.args[0], ast.Name):
                targets.add(node.args[0].id)
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "run_in_executor"
            and len(node.args) >= 2
            and isinstance(node.args[1], ast.Name)
        ):
            targets.add(node.args[1].id)
    return targets


@rule(
    "thread-loop-affinity",
    "worker-thread code mutates loop-owned state directly (futures, "
    "mailboxes, task spawns): marshal through loop.call_soon_threadsafe",
)
def _thread_loop_affinity(ctx: FileContext) -> None:
    targets = _thread_target_names(ctx)
    if not targets:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.FunctionDef) or node.name not in targets:
            continue
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr in _LOOP_AFFINE_ATTRS
            ):
                ctx.report(
                    "thread-loop-affinity", sub,
                    f".{sub.func.attr}(...) called from thread-target "
                    f"{node.name}() without call_soon_threadsafe",
                )


# --- pool-shutdown -----------------------------------------------------------

# Worker-pool constructors whose threads/processes outlive their owner
# unless someone shuts them down: a pool created per-request (or per
# node restart) without a shutdown path leaks OS threads until the
# process dies — invisible to the asyncio task-leak sweep, which only
# sees loop tasks.  ISSUE 10's parallel-extraction pool is the in-tree
# instance (Node.__aexit__ shuts it down).
_POOL_QUALS = {
    "concurrent.futures.ThreadPoolExecutor",
    "concurrent.futures.ProcessPoolExecutor",
    "multiprocessing.Pool",
    "multiprocessing.pool.ThreadPool",
}
_POOL_ATTRS = {"ThreadPoolExecutor", "ProcessPoolExecutor", "ThreadPool"}


def _is_pool_call(ctx: FileContext, call: ast.Call) -> bool:
    qual = ctx.resolve(call.func)
    return qual in _POOL_QUALS or (
        qual is not None and qual.split(".")[-1] in _POOL_ATTRS
    )


@rule(
    "pool-shutdown",
    "executor/worker pool created without a shutdown path in this file: "
    "its threads outlive the owner and leak per restart (call .shutdown()/"
    ".terminate()/.close()+.join(), or create it in a `with` block)",
)
def _pool_shutdown(ctx: FileContext) -> None:
    # A `with ThreadPoolExecutor(...) as p:` item manages its own
    # lifetime; so does entering a STORED pool later (`pool = ...;
    # with pool:`) — but only names actually assigned from a pool
    # constructor count, or any `with lock:` in the file would
    # suppress the rule (review finding: near-vacuous heuristics).
    managed: set[int] = set()
    pool_names: set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ) and _is_pool_call(ctx, node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    pool_names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    pool_names.add(t.attr)  # self.pool = ...
    with_pool_context = False
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                ce = item.context_expr
                if isinstance(ce, ast.Call):
                    managed.add(id(ce))
                elif isinstance(ce, ast.Name) and ce.id in pool_names:
                    with_pool_context = True
                elif (
                    isinstance(ce, ast.Attribute) and ce.attr in pool_names
                ):
                    with_pool_context = True
    # File-scope teardown (like thread-loop-affinity's heuristic):
    # .shutdown()/.terminate() anywhere; a bare .close() only counts
    # alongside a .join() (multiprocessing's canonical close()+join() —
    # an unrelated file.close() alone must not suppress the rule).
    attrs = {
        n.func.attr
        for n in ast.walk(ctx.tree)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
        # `sep.join(parts)` always takes a positional arg; a pool's
        # join() never does — don't let string plumbing count
        and (n.func.attr != "join" or not n.args)
    }
    has_shutdown = (
        with_pool_context
        or "shutdown" in attrs
        or "terminate" in attrs
        or ("close" in attrs and "join" in attrs)
    )
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or id(node) in managed:
            continue
        if _is_pool_call(ctx, node) and not has_shutdown:
            qual = ctx.resolve(node.func)
            name = (qual or "").split(".")[-1] or "pool"
            ctx.report(
                "pool-shutdown", node,
                f"{name}(...) created but this file never calls "
                ".shutdown()/.terminate()/.close()+.join() (and it is "
                "not a `with` target)",
            )


# --- metric-name / event-name ------------------------------------------------

_METRIC_ATTRS = {"inc", "observe", "set_gauge"}

# Registered telemetry layers: the `<layer>` half of every
# `<layer>.<name>` metric/span/event literal must come from this set, so
# a new subsystem's names are REGISTERED (here + OBSERVABILITY.md), not
# invented ad hoc — a typo'd or unregistered layer ("mempol.size") would
# otherwise ship a parallel namespace no dashboard ever reads.  ISSUE 5
# adds `mempool` (the mempool subsystem's metric/event/span names).
KNOWN_LAYERS = frozenset({
    "asyncsan",   # runtime sanitizers (tpunode/asyncsan.py)
    "bench",      # bench worker traces (bench.py)
    "blackbox",   # flight recorder (tpunode/blackbox.py, ISSUE 16)
    "bus",        # Publisher/user bus (tpunode/actors.py)
    "chain",      # header-chain actor (tpunode/chain.py)
    "chaos",      # fault injection (tpunode/chaos.py, ISSUE 7)
    "cpu",        # the process's CPU time by thread role
                  # (tpunode/asyncsan.py's collector, ISSUE 38)
    "events",     # event-log self-metrics (tpunode/events.py)
    "extract",    # the extractor's own phases: digests by kind, x-only key
                  # lifts, unsupported inputs (tpunode/node.py
                  # _count_extracted, ISSUE 42)
    "gc",         # the collector's pauses by generation
                  # (tpunode/asyncsan.py's gc.callbacks entry, ISSUE 38)
    "ibd",        # block-fetch-driven IBD planner (tpunode/ibd.py, ISSUE 11)
    "loop",       # the event loop's clock: idle, CPU, holds by name
                  # (tpunode/asyncsan.py, ISSUE 38)
    "mempool",    # mempool subsystem (tpunode/mempool.py)
    "mesh",       # pod-scale fleet: host health, sub-mesh shrink/regrow
                  # (tpunode/verify/engine.py, ISSUE 13; also the
                  # chaos mesh.dispatch injection point)
    "node",       # node composition/ingest (tpunode/node.py)
    "peer",       # wire sessions (tpunode/peer.py)
    "peermgr",    # fleet manager (tpunode/peermgr.py)
    "receipts",   # hash-chained verdict receipt log (tpunode/receipts.py,
                  # ISSUE 20)
    "sched",      # lane-packing verify scheduler (tpunode/verify/sched.py,
                  # ISSUE 10; incl. the node-side extract ring gauges)
    "serve",      # multi-tenant verification-as-a-service front-end
                  # (tpunode/serve.py, ISSUE 20)
    "slo",        # SLO engine: burn rates + budgets (tpunode/slo.py,
                  # ISSUE 17)
    "store",      # KV store (tpunode/store.py)
    "threadsan",  # lock-order/lockset sanitizer (tpunode/threadsan.py,
                  # ISSUE 18)
    "trace",      # tracing internals (tpunode/tracectx.py)
    "tsdb",       # metrics timeline sampler (tpunode/timeseries.py,
                  # ISSUE 16)
    "utxo",       # persistent UTXO store (tpunode/utxo.py, ISSUE 9)
    "verify",     # batch verify engine (tpunode/verify/)
    "watchdog",   # stall watchdog (tpunode/watchdog.py)
})


def _name_violation(name: str) -> "str | None":
    """Schema complaint for a metric/span/event name literal, or None."""
    if not NAME_SCHEMA_RE.match(name):
        return f"{name!r} violates <layer>.<name> schema"
    layer = name.split(".", 1)[0]
    if layer not in KNOWN_LAYERS:
        return (
            f"{name!r} uses unregistered layer {layer!r} (register in "
            "analysis.rules.KNOWN_LAYERS + OBSERVABILITY.md)"
        )
    return None


def _literal(node: ast.AST) -> "str | None":
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


@rule(
    "metric-name",
    "metric/span name literal violates the `<layer>.<name>` schema "
    "(^[a-z]+(\\.[a-z_]+)+$ with a registered layer, OBSERVABILITY.md)",
)
def _metric_name(ctx: FileContext) -> None:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        lit = _literal(node.args[0]) if node.args else None
        hit = None
        if isinstance(func, ast.Attribute) and func.attr in _METRIC_ATTRS:
            hit = lit
        elif isinstance(func, ast.Name) and func.id == "span":
            hit = lit
        elif isinstance(func, ast.Attribute) and func.attr == "span":
            hit = lit  # module-qualified form: trace.span("...")
        elif isinstance(func, ast.Attribute) and func.attr == "inc_batch":
            # inc_batch takes ((name, delta, labels), ...): lint every
            # literal tuple's literal first element (the old regex lint
            # never saw these)
            for arg in node.args:
                if isinstance(arg, (ast.Tuple, ast.List)):
                    for el in arg.elts:
                        if isinstance(el, (ast.Tuple, ast.List)) and el.elts:
                            name = _literal(el.elts[0])
                            why = (
                                _name_violation(name)
                                if name is not None else None
                            )
                            if why is not None:
                                ctx.report(
                                    "metric-name", el,
                                    f"metric name {why}",
                                )
            continue
        if hit is not None:
            why = _name_violation(hit)
            if why is not None:
                ctx.report("metric-name", node, f"metric name {why}")


@rule(
    "event-name",
    "event-type literal at .emit() violates the `<layer>.<name>` schema "
    "(registered layer required, no grandfathered names)",
)
def _event_name(ctx: FileContext) -> None:
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "emit"
            and node.args
        ):
            lit = _literal(node.args[0])
            why = _name_violation(lit) if lit is not None else None
            if why is not None:
                ctx.report("event-name", node, f"event type {why}")


# --- label-cardinality -------------------------------------------------------

# Registered bounded label-value sources: helpers whose return set is
# fixed and small by construction, so a label value drawn from one
# cannot grow series cardinality.  ``multichip.host_names`` is the
# canonical fleet-name source (ISSUE 19): AffinityMap seeds hash the
# name strings, so every layer that labels by host must already route
# through it — which is exactly what makes it safe to allowlist.
# ``serve.tenant_names`` (ISSUE 20) is its tenant-registry twin: it
# validates and bounds the tenant set (<= serve.MAX_TENANTS, pinned name
# charset), so a ``tenant=`` value drawn from it cannot grow series.
_BOUNDED_LABEL_SOURCES = frozenset({"host_names", "tenant_names"})


def _dynamic_format(expr: ast.AST) -> bool:
    """Is this expression a dynamically-formatted string — an f-string
    with interpolation, a ``.format(...)`` call, or a ``%`` format?"""
    if isinstance(expr, ast.JoinedStr):
        return any(
            isinstance(v, ast.FormattedValue) for v in expr.values
        )
    if (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Attribute)
        and expr.func.attr == "format"
    ):
        return True
    return (
        isinstance(expr, ast.BinOp)
        and isinstance(expr.op, ast.Mod)
        and isinstance(expr.left, ast.Constant)
        and isinstance(expr.left.value, str)
    )


def _has_bounded_call(ctx: FileContext, expr: ast.AST) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            qual = ctx.resolve(n.func) or ""
            if qual.split(".")[-1] in _BOUNDED_LABEL_SOURCES:
                return True
    return False


def _binding_index(ctx: FileContext) -> dict:
    """name -> every expression bound to it anywhere in the file
    (assignments, loop targets, comprehension targets).  File-wide on
    purpose: for a lint, over-approximation beats scope bookkeeping —
    an unbounded formatted binding ANYWHERE taints the name unless a
    bounded source also feeds it."""
    out: dict = {}

    def bind(target: ast.AST, expr: ast.AST) -> None:
        if isinstance(target, ast.Name):
            out.setdefault(target.id, []).append(expr)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                bind(el, expr)
        elif isinstance(target, ast.Starred):
            bind(target.value, expr)

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                bind(t, node.value)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            bind(node.target, node.value)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            bind(node.target, node.iter)
        elif isinstance(node, ast.comprehension):
            bind(node.target, node.iter)
    return out


def _labeled_metric_calls(ctx: FileContext):
    """Yield ``(report_node, labels_expr)`` for every labeled metric
    call: the ``labels=`` keyword of inc/observe/set_gauge, and the
    third element of each literal inc_batch tuple."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not isinstance(
            node.func, ast.Attribute
        ):
            continue
        if node.func.attr in _METRIC_ATTRS:
            for kw in node.keywords:
                if kw.arg == "labels" and kw.value is not None:
                    yield node, kw.value
        elif node.func.attr == "inc_batch":
            for arg in node.args:
                if isinstance(arg, (ast.Tuple, ast.List)):
                    for el in arg.elts:
                        if (
                            isinstance(el, (ast.Tuple, ast.List))
                            and len(el.elts) >= 3
                        ):
                            yield el, el.elts[2]


@rule(
    "label-cardinality",
    "dynamically-formatted label value on a metric without a bounded "
    "source (series cardinality = label-value cardinality: route fleet "
    "names through multichip.host_names, or pin the value set)",
)
def _label_cardinality(ctx: FileContext) -> None:
    """ISSUE 19 satellite: a labeled series is born per distinct label
    value, and the registry/Timeline only stay bounded when every label
    value comes from a bounded set (fixed hosts, declared SLOs, enum
    classes).  An f-string/``.format``/``%``-formatted value is the
    canonical unbounded-source smell — flag it unless the formatted
    input demonstrably comes from a registered bounded helper
    (``_BOUNDED_LABEL_SOURCES``).

    ISSUE 20 extension: the ``tenant=`` label key additionally gets a
    POSITIVE check — its value must be a string literal, or visibly
    trace to the bounded tenant registry (``serve.tenant_names``),
    because tenant names arrive from config/wire input where a merely
    not-formatted value is no evidence of boundedness."""
    bindings: "dict | None" = None

    def get_bindings() -> dict:
        nonlocal bindings
        if bindings is None:
            bindings = _binding_index(ctx)
        return bindings

    def taint(expr: ast.AST) -> "str | None":
        if _dynamic_format(expr):
            return "is dynamically formatted inline"
        if isinstance(expr, ast.Name):
            bound = get_bindings().get(expr.id, [])
            if any(_has_bounded_call(ctx, e) for e in bound):
                return None
            if any(_dynamic_format(e) for e in bound):
                return (
                    f"is bound to a dynamically formatted value "
                    f"({expr.id!r})"
                )
        return None

    def unbounded_tenant(v: ast.AST) -> bool:
        """True when a ``tenant=`` value shows no bounded provenance:
        not a literal, no inline ``tenant_names(...)`` call, and no
        file-wide binding of the name routed through one."""
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return False
        if _has_bounded_call(ctx, v):
            return False
        if isinstance(v, ast.Name):
            bound = get_bindings().get(v.id, [])
            if any(_has_bounded_call(ctx, e) for e in bound):
                return False
        return True

    for node, labels in _labeled_metric_calls(ctx):
        dicts = []
        if isinstance(labels, ast.Dict):
            dicts.append(labels)
        elif isinstance(labels, ast.Name):
            # labels passed by name: lint the dict literal(s) the name
            # was assigned, but report at the metric call (that is
            # where the pragma belongs)
            dicts.extend(
                e for e in get_bindings().get(labels.id, [])
                if isinstance(e, ast.Dict)
            )
        for d in dicts:
            for k_node, v in zip(d.keys, d.values):
                key = _literal(k_node) if k_node is not None else None
                why = taint(v)
                if why is None and key == "tenant" and unbounded_tenant(v):
                    why = (
                        "does not visibly trace to the bounded tenant "
                        "registry (serve.tenant_names)"
                    )
                if why is not None:
                    ctx.report(
                        "label-cardinality", node,
                        f"label {key or '?'!r} value {why} — label "
                        "values must come from a bounded source "
                        "(register one in _BOUNDED_LABEL_SOURCES, or "
                        "pin the set)",
                    )


# --- doc-drift ---------------------------------------------------------------

# OBSERVABILITY.md relative to this file (tpunode/analysis/ -> repo
# root).  Loaded once per process; a missing doc disables the rule (an
# installed copy of the package without the repo docs must lint clean).
_OBS_DOC_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..",
    "OBSERVABILITY.md",
)
_obs_doc_cache: list = []  # [str] once loaded, [None] when absent


def _observability_text() -> "str | None":
    if not _obs_doc_cache:
        try:
            with open(_OBS_DOC_PATH, encoding="utf-8") as f:
                _obs_doc_cache.append(f.read())
        except OSError:
            _obs_doc_cache.append(None)
    return _obs_doc_cache[0]


def _telemetry_name_literals(ctx: FileContext):
    """Yield ``(node, name)`` for every literal metric/span/event name in
    the file — the exact call sites metric-name/event-name lint."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        first = _literal(node.args[0]) if node.args else None
        if isinstance(func, ast.Attribute):
            if func.attr in _METRIC_ATTRS or func.attr in ("span", "emit"):
                if first is not None:
                    yield node, first
            elif func.attr == "inc_batch":
                for arg in node.args:
                    if isinstance(arg, (ast.Tuple, ast.List)):
                        for el in arg.elts:
                            if isinstance(
                                el, (ast.Tuple, ast.List)
                            ) and el.elts:
                                name = _literal(el.elts[0])
                                if name is not None:
                                    yield el, name
        elif (
            isinstance(func, ast.Name)
            and func.id == "span"
            and first is not None
        ):
            yield node, first


@rule(
    "doc-drift",
    "schema-valid telemetry name literal is absent from OBSERVABILITY.md "
    "(every shipped metric/span/event name needs an inventory row)",
)
def _doc_drift(ctx: FileContext) -> None:
    """ISSUE 16 satellite: the names inventory in OBSERVABILITY.md is
    load-bearing (dashboards and the flight-recorder postmortems are
    read against it), so a name shipped without a row is drift, caught
    at lint time.  Only names that PASS the schema+layer checks are
    considered — a malformed name is metric-name/event-name's finding,
    not two findings for one mistake."""
    doc = _observability_text()
    if doc is None:
        return
    for node, name in _telemetry_name_literals(ctx):
        if _name_violation(name) is not None:
            continue
        if name not in doc:
            ctx.report(
                "doc-drift", node,
                f"telemetry name {name!r} is not documented in "
                "OBSERVABILITY.md (add an inventory row)",
            )


# --- stale-doc ---------------------------------------------------------------

# doc-drift's reverse pass (ISSUE 17): an OBSERVABILITY.md inventory row
# whose name no code literal ships anymore is a lie dashboards are still
# being read against.  The scan is scoped to the regions that CLAIM to be
# an inventory — the "Current inventory by layer" bullet list and the
# pipe-table rows whose first cell is backticked (the events/pieces
# tables) — so prose elsewhere in the doc cannot false-positive.

_DOC_TOKEN_RE = re.compile(r"`([^`]+)`")
# Same pragma as core's, re-parsed here for MARKDOWN rows: the doc form
# lives in an HTML comment (`<!-- # asyncsan: disable=stale-doc -->`),
# so the token list must stop at whitespace rather than swallowing the
# comment terminator's hyphens.
_DOC_PRAGMA_RE = re.compile(r"#\s*asyncsan:\s*disable=([A-Za-z0-9_\-,]+)")

# Repo root relative to this file; the code corpus the doc is checked
# against is every .py under tpunode/ and benchmarks/ plus the driver.
_REPO_ROOT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)
_corpus_cache: list = []  # [str] once loaded (concatenated sources)


def _code_corpus() -> str:
    if not _corpus_cache:
        paths = [os.path.join(_REPO_ROOT, "bench.py")]
        for top in ("tpunode", "benchmarks"):
            for root, dirs, names in os.walk(os.path.join(_REPO_ROOT, top)):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                paths.extend(
                    os.path.join(root, f)
                    for f in sorted(names)
                    if f.endswith(".py")
                )
        chunks = []
        for path in paths:
            try:
                with open(path, encoding="utf-8") as f:
                    chunks.append(f.read())
            except OSError:
                pass
        _corpus_cache.append("\n".join(chunks))
    return _corpus_cache[0]


def _doc_documented_names(doc: str):
    """Yield ``(lineno, line, name)`` for every schema-valid telemetry
    name the doc's inventory regions commit to.  Labeled forms are
    stripped at ``{`` (``peer.msgs{peer=,cmd=}`` documents ``peer.msgs``)
    and ``.py`` path tokens are skipped (module tables, not telemetry)."""
    inventory = False
    for lineno, line in enumerate(doc.splitlines(), start=1):
        stripped = line.strip()
        if stripped.startswith("Current inventory by layer"):
            inventory = True
            continue
        if inventory and stripped.startswith("## "):
            inventory = False
        if not inventory and not stripped.startswith("| `"):
            continue
        for token in _DOC_TOKEN_RE.findall(line):
            name = token.split("{", 1)[0]
            if name.endswith(".py") or not NAME_SCHEMA_RE.match(name):
                continue
            yield lineno, line, name


@rule(
    "stale-doc",
    "OBSERVABILITY.md inventory row names a telemetry series no code "
    "literal ships anymore (delete the row, or suppress the row with "
    "`<!-- # asyncsan: disable=stale-doc -->` if it is intentional)",
)
def _stale_doc(ctx: FileContext) -> None:
    """Runs once per analysis (anchored on this file, which every full
    tree sweep includes) rather than per analyzed file.  Findings carry
    the DOC's path+line, so they are appended directly instead of going
    through ctx.report — per-row suppression is the pragma on the doc
    row itself, not on any Python line."""
    if not ctx.path.replace(os.sep, "/").endswith("analysis/rules.py"):
        return
    doc = _observability_text()
    if doc is None:
        return
    corpus = _code_corpus()
    seen: set[str] = set()
    for lineno, line, name in _doc_documented_names(doc):
        if name in seen:
            continue
        seen.add(name)
        m = _DOC_PRAGMA_RE.search(line)
        if m is not None:
            ids = {t.strip().rstrip("-") for t in m.group(1).split(",")}
            if "all" in ids or "stale-doc" in ids:
                continue
        # span-histogram rows document the landed name; the literal at
        # the call site is the bare span("<layer>.<name>") argument
        bare = name[len("span."):] if name.startswith("span.") else name
        if name in corpus or bare in corpus:
            continue
        ctx.findings.append(
            Finding(
                rule="stale-doc",
                path=os.path.normpath(_OBS_DOC_PATH),
                line=lineno,
                col=0,
                message=(
                    f"documented telemetry name {name!r} no longer "
                    "appears as a code literal (stale inventory row)"
                ),
            )
        )


# --- raw-lock (ISSUE 18) ------------------------------------------------------


@rule(
    "raw-lock",
    "bare threading.Lock()/RLock() construction bypasses the threadsan "
    "registry (use tpunode.threadsan.lock()/rlock() so the lock is "
    "named, hold-timed, and deadlock-checked)",
)
def _raw_lock(ctx: FileContext) -> None:
    """Every lock in the tree goes through threadsan's LockRegistry —
    that is what makes the lock-order graph complete.  threadsan.py
    itself is exempt (its wrappers and the registry's one meta lock are
    the raw primitives everything else is built on)."""
    base = os.path.basename(ctx.path.replace(os.sep, "/"))
    if base == "threadsan.py":
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        qual = ctx.resolve(func)
        hit = qual in ("threading.Lock", "threading.RLock")
        if (
            not hit
            and qual is None
            and isinstance(func, ast.Attribute)
            and func.attr in ("Lock", "RLock")
            and isinstance(func.value, ast.Call)
        ):
            # dynamic receiver, e.g. __import__("threading").Lock()
            hit = True
        if hit:
            kind = (func.attr if isinstance(func, ast.Attribute)
                    else qual.rsplit(".", 1)[-1])
            ctx.report(
                "raw-lock", node,
                f"bare threading.{kind}() outside the threadsan registry "
                "(construct via tpunode.threadsan."
                f"{'rlock' if kind == 'RLock' else 'lock'}('<layer>.<name>') "
                "so it joins the lock-order graph)",
            )


# --- env-knob-doc (ISSUE 18) --------------------------------------------------

_ENV_KNOB_RE = re.compile(r"^TPUNODE_[A-Z0-9_]+$")


@rule(
    "env-knob-doc",
    "TPUNODE_* env knob literal is missing from OBSERVABILITY.md's "
    "env-var inventory (every shipped knob needs an inventory row)",
)
def _env_knob_doc(ctx: FileContext) -> None:
    """Same doc-drift contract as the telemetry inventory, for config
    knobs: an operator reading OBSERVABILITY.md must see every env var
    the tree actually reads.  Containment is whole-doc (a prose mention
    counts), so one inventory row per knob is the cheap fix."""
    doc = _observability_text()
    if doc is None:
        return
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and _ENV_KNOB_RE.match(node.value)
            and node.value not in doc
        ):
            ctx.report(
                "env-knob-doc", node,
                f"env knob {node.value!r} is not documented in "
                "OBSERVABILITY.md (add an env-var inventory row)",
            )
