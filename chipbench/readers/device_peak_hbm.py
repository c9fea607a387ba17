"""Peak device memory on the fullest chip, in MB (``memory_stats()``)."""


def read(reading):
    peak = reading.device.get("memory_peak_bytes")
    return peak / 1e6 if peak else None
