"""The ``q`` quantile of the driver's own samples ``samples`` over the
window (host clock); nothing where there are fewer than ``at_least``."""

from chipbench import harness


def read(reading, samples: str, q: float, at_least: int = 1):
    values = reading.samples.get(samples, ())
    if len(values) < max(1, at_least):
        return None
    return harness.quantile(values, q)
