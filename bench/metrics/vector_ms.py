"""Device milliseconds per CG iteration of the solver's vector passes (the
dots, AXPYs and stop test under the program's ``cg/vector`` scope), over
the cell's own traced whole solves."""


def read(record):
    c = record.clocks
    if "cg/vector_s" not in c or not c.get("traced_iters"):
        return None
    return 1e3 * c["cg/vector_s"] / c["traced_iters"]
