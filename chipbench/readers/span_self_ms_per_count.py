"""Milliseconds of a host span's self time per unit of a counter: the time
the span ``span`` was open less the time its child spans ``children`` were
(spans that lie inside it in time, on any thread).  Nothing where the
program has no such span."""


def read(reading, span: str, children: list, per: list):
    n = sum(reading.counters.get(c, 0.0) for c in per)
    key = f"span.{span}.seconds"
    if key not in reading.counters or not n:
        return None
    inside = sum(reading.counters.get(f"span.{c}.seconds", 0.0)
                 for c in children)
    return 1e3 * (reading.counters[key] - inside) / n
