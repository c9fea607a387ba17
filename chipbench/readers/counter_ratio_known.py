"""``counter_ratio`` for a numerator that is new: nothing where the program
has none of the ``num`` counters (``counter_ratio`` reads 0 there, which a
commit that lacks the counter and a window in which it did not move would
share)."""

from chipbench.readers import counter_ratio


def read(reading, num: list, den: list, scale: float = 1.0):
    if not any(n in reading.counters for n in num):
        return None
    return counter_ratio.read(reading, num, den, scale)
