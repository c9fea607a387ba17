"""The plain reference of the network: what a link of a given round-trip
time and uplink can have sent, and when.

Independent of the emulator (``peers_wan.py``): it reads the links' table
from the configuration file and each link's log — when a connection was
accepted, when each request's last byte arrived, when each piece was
handed to the socket — and works the earliest moment every byte may have
left out again, its own way:

* replies leave in the order they were sent; reply i may start at
  ``max(request_i + rtt, end of reply i-1)`` and its byte x leaves x / rate
  later.  A piece stands for the bytes up to its end, so it may not have
  gone before its last byte's moment;
* a block's first piece may not have gone before its ``getdata`` + rtt;
* no link carries more than its rate in any whole second: a piece of n
  bytes handed over at t stands for bytes that left over the n / rate
  before t;
* a ``stall`` fault at moment m: from m on the peer completes no block
  frame, and sends at most one piece of a block per connection (the one
  that leaves the frame half sent); one such cut lies inside the window;
* a ``reset`` fault at moment m: at or after m a connection of the peer
  ends with a block frame half sent, inside the window, and a later
  connection is accepted.

``check`` returns counts that must all read 0.
"""

from __future__ import annotations

import collections

EPS = 1e-6  # seconds: float noise, not a tolerance of the model


def _replies(log: list) -> list:
    """The frames a link sent, in order: ``{conn, kind, key, pieces: [(t,
    end offset)], length}``."""
    out, cur = [], {}
    for ev in log:
        if ev[0] != "piece":
            continue
        _, t, conn, kind, key, n, end, length = ev
        rep = cur.get(conn)
        if rep is None or end - n == 0:
            rep = cur[conn] = {"conn": conn, "kind": kind, "key": key,
                               "pieces": [], "length": length}
            out.append(rep)
        rep["pieces"].append((t, end))
    return out


def check_link(link: dict, log: list, armed_at: float, window: tuple) -> dict:
    rtt = link["rtt_ms"] / 1e3
    rate = link["uplink_mbit_s"] * 1e6 / 8
    accepted = {ev[2]: ev[1] for ev in log if ev[0] == "accept"}
    asked = collections.defaultdict(collections.deque)  # (conn, hash) -> times
    pings = collections.defaultdict(collections.deque)
    for ev in log:
        if ev[0] == "request":
            _, t, conn, cmd, hashes = ev
            if cmd == "ping":
                pings[conn].append(t)
            for h in hashes:
                asked[conn, h].append(t)

    early_first = early_piece = unasked = 0
    ends = {}  # conn -> when its reply before this one may have ended
    replies = _replies(log)
    for rep in replies:
        conn = rep["conn"]
        if rep["kind"] == "block":
            times = asked[conn, rep["key"]]
            if not times:
                unasked += 1
                continue
            t_req = times.popleft()
        elif rep["kind"] == "pong":
            t_req = pings[conn].popleft() if pings[conn] else accepted[conn]
        else:  # version, verack, headers: not before the connection + rtt
            t_req = accepted[conn]
        start = max(t_req + rtt, ends.get(conn, 0.0))
        if rep["kind"] == "block" and rep["pieces"][0][0] < t_req + rtt - EPS:
            early_first += 1
        for t, end in rep["pieces"]:
            early_piece += t < start + end / rate - EPS
        ends[conn] = start + rep["pieces"][-1][1] / rate

    # whole seconds from the link's first piece on
    pieces = sorted((ev[1], ev[5]) for ev in log if ev[0] == "piece")
    over = 0
    if pieces:
        t0 = pieces[0][0] - pieces[0][1] / rate
        bins = collections.Counter()
        for t, n in pieces:
            a, b = t - n / rate, t  # the bytes left over [a, b]
            k = int(a - t0)
            while k <= int(b - t0):
                lo, hi = max(a, t0 + k), min(b, t0 + k + 1)
                if hi > lo:
                    bins[k] += n * (hi - lo) / (b - a)
                k += 1
        over = sum(v > rate * (1 + 1e-9) + 1 for v in bins.values())

    out = {"blocks_sooner_than_one_rtt_after_their_request": early_first,
           "pieces_sooner_than_the_uplink_allows": early_piece,
           "blocks_nobody_asked_for": unasked,
           "seconds_over_the_uplink": over}
    fault = link.get("fault")
    if fault is None:
        return out
    moment = armed_at + fault["at_s"]
    opened, closed = window
    blocks = [r for r in replies if r["kind"] == "block"]
    # frames left half sent, by the moment of their last piece
    cuts = [r["pieces"][-1][0] for r in blocks
            if r["pieces"][-1][1] < r["length"]]
    fired = [t for t in cuts if t >= moment - EPS]
    inside = [t for t in fired if opened <= t <= closed]
    out["fault_outside_the_window"] = int(not inside)
    if fault["kind"] == "stall":
        after = collections.Counter()
        whole = 0
        for r in blocks:
            for t, end in r["pieces"]:
                if t >= moment - EPS:
                    after[r["conn"]] += 1
                    whole += end == r["length"]
        out["staller_block_pieces_after_its_moment"] = whole + sum(
            n - 1 for n in after.values())
    else:
        first = min(fired, default=None)
        back = [t for t in accepted.values() if first is not None and t > first]
        out["reset_peer_not_back"] = int(not back)
    return out


def check(links: list, logs: list, armed_at: float, window: tuple) -> dict:
    """Every link against its log; counts summed over the links."""
    total = collections.Counter()
    for link, log in zip(links, logs):
        total.update(check_link(link, log, armed_at, window))
    return dict(total)
