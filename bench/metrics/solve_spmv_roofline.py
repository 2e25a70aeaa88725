"""Percent of its roofline that the SpMV inside the solve reaches: the
least time any f32 implementation needs for one call
(``bench.work.spmv_min_bytes`` at the device's HBM peak) over the device
time per call under the program's ``cg/spmv`` scope in the cell's own traced
whole solves, one call per CG iteration."""

from bench.work import peaks, spmv_min_bytes


def read(record):
    c = record.clocks
    if not c.get("cg/spmv_s") or not c.get("traced_iters"):
        return None
    w = record.work
    least = spmv_min_bytes(w["nnz"], w["nrows"], w["ncols"]) / peaks(
        record.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (c["cg/spmv_s"] / c["traced_iters"])
