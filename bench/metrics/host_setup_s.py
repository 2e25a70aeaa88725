"""Seconds of host set-up: the driver's build of the matrix, hierarchy or
graph and of the right-hand side, before the tuner (host clock)."""


def read(record):
    return record.clocks.get("host_setup_s")
