"""sum(num counters) / sum(den counters) over the window, times ``scale``."""


def read(reading, num: list, den: list, scale: float = 1.0):
    d = sum(reading.counters.get(n, 0.0) for n in den)
    if not d:
        return None
    return scale * sum(reading.counters.get(n, 0.0) for n in num) / d
