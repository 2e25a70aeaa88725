"""Seconds in the program's run-first tuner: the races, with the candidate
containers each race converts (host clock)."""


def read(record):
    return record.clocks.get("tune_s")
