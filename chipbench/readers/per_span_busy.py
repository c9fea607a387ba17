"""Work per second of a host span's busy time: sum(counters) over the
seconds the span ``span`` was open (host clock, the program's own span)."""


def read(reading, counters: list, span: str):
    busy = reading.counters.get(f"span.{span}.seconds", 0.0)
    work = sum(reading.counters.get(n, 0.0) for n in counters)
    if not busy or not work:
        return None
    return work / busy
