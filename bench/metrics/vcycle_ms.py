"""Device milliseconds of one application of the tuned multigrid V-cycle,
the mean of the traced probe calls (each output feeding the next input)."""


def read(record):
    t = record.probe_s.get("vcycle")
    return None if t is None else 1e3 * t
