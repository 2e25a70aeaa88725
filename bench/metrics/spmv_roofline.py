"""Percent of its roofline that one SpMV of the finest tuned operator
reaches: the least time any f32 implementation needs for the call
(``bench.work.spmv_min_bytes`` at the device's HBM peak) over the device
time of the traced probe calls (each output feeding the next input)."""

from bench.work import peaks, spmv_min_bytes


def read(record):
    t = record.probe_s.get("spmv")
    if not t:
        return None
    w = record.work
    least = spmv_min_bytes(w["nnz"], w["nrows"], w["ncols"]) / peaks(
        record.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / t
