"""Counters over the window's length, times ``scale``: a share of the
window at 100, a rate a minute at 60.  With ``complement`` it is what the
counters leave of the window.  Nothing where the program has none of the
counters (``complement``: where it lacks any), so a commit without them
reads nothing, not 0."""


def read(reading, counters: list, scale: float = 100.0,
         complement: bool = False):
    have = [c for c in counters if c in reading.counters]
    if not reading.window_s or not have:
        return None
    if complement and len(have) < len(counters):
        return None
    total = sum(reading.counters[c] for c in have)
    if complement:
        total = reading.window_s - total
    return scale * total / reading.window_s
