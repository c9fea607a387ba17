"""The seconds a host span was open as a percentage of the window (host
clock, the program's own span).  Nothing where the program has no such
span.  A wait span is held across ``await``s, so one open at an edge of
the window counts whole in the window it closes in."""


def read(reading, span: str):
    key = f"span.{span}.seconds"
    if key not in reading.counters or not reading.window_s:
        return None
    return 100.0 * reading.counters[key] / reading.window_s
