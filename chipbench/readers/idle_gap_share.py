"""The device's idle seconds that the trace's breakdown gives to the host
span ``span`` (``none``: to no span at all) as a percentage of the traced
window.  The breakdown lists its ten largest entries: a span the program
has that is not among them reads 0.  Nothing without a trace, or where
the program has no such span."""


def read(reading, span: str):
    if reading.trace is None:
        return None
    if span != "none" and f"span.{span}.count" not in reading.counters:
        return None
    gaps = dict(reading.trace["breakdown"]["idle_gaps"])
    return 100.0 * gaps.get(span, 0.0) / reading.trace["window_s"]
