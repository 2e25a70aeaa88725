"""Percent of the device's busy time in the cell's own traced whole solves
spent in ops that lie under one of the program's layer scopes
(``repro.core.obs.LAYER_SCOPES``): how much of the solve the per-scope
metrics can see."""


def read(record):
    c = record.clocks
    if "scoped_s" not in c or not c.get("busy_s"):
        return None
    return 100.0 * c["scoped_s"] / c["busy_s"]
