"""Milliseconds a host span was open per unit of a counter."""


def read(reading, span: str, per: list):
    n = sum(reading.counters.get(c, 0.0) for c in per)
    if not n:
        return None
    return 1e3 * reading.counters.get(f"span.{span}.seconds", 0.0) / n
