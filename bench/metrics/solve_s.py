"""Seconds per solve: the measured window's length over the solves it
completed (host clock, from the first dispatch to the last result)."""


def read(record):
    return record.solve_s
