"""Percent of the traced solves' window in which no operation ran on the
device: 100 * (1 - busy / window), busy being the union of the device's
op intervals."""


def read(record):
    busy, window = record.device.get("busy_s"), record.device.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)
