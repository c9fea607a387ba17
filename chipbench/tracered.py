"""From a profiler trace (``.xplane.pb``) to device busy time, a per-op
table, the kernels' device time, and idle gaps laid against the host's
spans.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation.  Busy is the union of those intervals.  The
program's spans arrive as ``TraceAnnotation`` events on the host plane, on
the same clock, so each idle gap can be given to the span that covers most
of it.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNELS = ("verify_blocked",)

# Whom an idle gap is given to: the span of the first tier that covers
# most of it.  The dispatch thread's leaf spans come first (what the
# thread that feeds the device was doing), then the stages upstream of it;
# spans that merely contain those (verify.dispatch) come last.
TIERS = (
    ("verify.prepare", "verify.transfer", "verify.kernel", "verify.readback"),
    ("node.extract", "node.commit", "peer.decode", "peer.payload"),
)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise SystemExit(f"chipbench: no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap(merged: list, starts: list, s: float, e: float) -> float:
    """Length of ``[s, e]`` covered by a merged interval list."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def lane_slots(name: str):
    """The lane width in an op's name: its first shape's last extent
    (its output comes first: ``..._s32_1_4096_`` or ``s32[1,4096]``)."""
    m = re.findall(r"(?:\[|_)(\d+(?:[,_]\d+)*)(?:\]|_)", name)
    for shape in m:
        dims = [int(d) for d in re.split(r"[,_]", shape)]
        if dims and dims[-1] >= 8:
            return dims[-1]
    return None


def whole_runs(evs: list, runs: list, kernels: dict) -> None:
    """Add one chip's kernel time and lane slots to ``kernels``, over whole
    program runs only.  One run (an ``XLA Modules`` event) is one lane,
    however many kernel calls it holds: its slots count once.  A capture's
    edge cuts a run: its kernel events are then fewer than the other runs'
    or lie outside any run, and counting the lane against part of its
    kernel time would read too fast.  Such a run is left out, lane and
    time both."""
    starts = [s for s, _ in runs]
    per_run = collections.defaultdict(list)
    for s, e, op in evs:
        for k in KERNELS:
            if k in op:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][1]:
                    per_run[k, i].append(((e - s) / 1e9, lane_slots(op) or 0))
    for k in KERNELS:
        counts = collections.Counter(
            len(v) for (kk, _), v in per_run.items() if kk == k)
        if not counts:
            continue
        whole = max(counts, key=lambda c: (counts[c], c))
        for (kk, _), v in per_run.items():
            if kk == k and len(v) == whole:
                kernels[k]["device_s"] += sum(d for d, _ in v)
                kernels[k]["events"] += len(v)
                kernels[k]["lanes"] += 1
                kernels[k]["slots"] += max(w for _, w in v)


def reduce(path: str, spans: list = (), top: int = 10) -> dict:
    """``path``: a trace directory or an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    chips, modules, host = {}, {}, collections.defaultdict(list)
    lo, hi = float("inf"), 0.0
    wanted = set(spans)
    inventory = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for ln in plane.lines:
            events = list(ln.events)
            if events:
                inventory.append([plane.name, ln.name, len(events)])
            for ev in events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo, hi = min(lo, s), max(hi, e)
                if is_dev and ln.name == OPS_LINE:
                    chips.setdefault(plane.name, []).append((s, e, ev.name))
                elif is_dev and ln.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).append((s, e))
                elif not is_dev and ev.name in wanted:
                    host[ev.name].append((s, e))
    if not chips:
        raise SystemExit("chipbench: the trace holds no device operation")
    window_s = (hi - lo) / 1e9
    ops = collections.Counter()
    kernels = {k: {"device_s": 0.0, "events": 0, "lanes": 0, "slots": 0}
               for k in KERNELS}
    busy_by_chip, gaps = [], []
    for name in sorted(chips):
        evs = chips[name]
        merged = merge([(s, e) for s, e, _ in evs])
        busy_by_chip.append(sum(e - s for s, e in merged) / 1e9)
        for s, e, op in evs:
            ops[op] += (e - s) / 1e9
        whole_runs(evs, sorted(modules.get(name, ())), kernels)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host_merged = {n: merge(iv) for n, iv in host.items()}
    host_starts = {n: [iv[0] for iv in m] for n, m in host_merged.items()}
    idle = collections.Counter()
    for s, e in gaps:
        cover = {n: overlap(m, host_starts[n], s, e)
                 for n, m in host_merged.items()}
        best = "none"
        for tier in (*TIERS, tuple(cover)):
            most = [n for n in tier if cover.get(n, 0.0) > 0.5 * (e - s)]
            if most:
                best = max(most, key=cover.get)
                break
        idle[best] += (e - s) / 1e9 / len(chips)
    return {
        "window_s": window_s,
        "busy_s_by_chip": busy_by_chip,
        "busy_s": sum(busy_by_chip) / len(busy_by_chip),
        "kernels": kernels,
        "lines": inventory,
        "breakdown": {
            "device_ops": [[re.sub(r"[^A-Za-z0-9_.-]", "_", n)[:80], s]
                           for n, s in ops.most_common(top)],
            "idle_gaps": [[n, s] for n, s in idle.most_common(top)],
        },
    }
