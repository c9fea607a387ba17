"""100 * (1 - device busy / traced window), the mean over the chips used."""


def read(reading):
    if reading.trace is None:
        return None
    busy = reading.trace["busy_s_by_chip"]
    return 100.0 * (1.0 - sum(busy) / len(busy) / reading.trace["window_s"])
