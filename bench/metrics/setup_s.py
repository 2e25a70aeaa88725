"""Seconds from the start of the process until the window could start:
building, placing, tuning, compiling and one warm solve (host clock)."""


def read(record):
    return record.setup_s
