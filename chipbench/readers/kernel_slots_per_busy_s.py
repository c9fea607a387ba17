"""Lane slots the kernel was given per second of its device time: both
from the trace (the kernel's events and the lane width in their names)."""


def read(reading, kernel: str):
    if reading.trace is None:
        return None
    k = reading.trace["kernels"].get(kernel)
    if not k or not k["device_s"] or not k["slots"]:
        return None
    return k["slots"] / k["device_s"]
