"""The sum of some counters over the window (a count that must read 0, say)."""


def read(reading, counters: list):
    return float(sum(reading.counters.get(n, 0.0) for n in counters))
