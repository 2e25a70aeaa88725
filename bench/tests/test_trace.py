import json
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).with_name("data")


def _toy():
    # one device: ops [0,10) [5,20) [30,40) [45,50); host spans around them
    tr = T.Trace()
    tr.ops["/device:TPU:0"] = [("fusion.1", 0, 10), ("fusion.2", 5, 20),
                               ("fusion.1", 30, 40), ("copy", 45, 50)]
    tr.spans = [("solve", 0, 25), ("between_solves", 25, 28), ("solve", 28, 50)]
    return tr


def test_busy_is_the_union_of_op_intervals():
    tr = _toy()
    ops = tr.ops["/device:TPU:0"]
    assert T.merged(ops, 0, 50) == [(0, 20), (30, 40), (45, 50)]
    assert T.busy_ns(ops, 0, 50) == 35
    assert T.busy_ns(ops, 8, 35) == 17  # clipped at both ends
    assert T.gaps(ops, 0, 50) == [(20, 30), (40, 45)]
    assert T.gaps(ops, 0, 60)[-1] == (50, 60)


def test_idle_share_and_breakdown():
    tr = _toy()
    s = T.device_summary(tr, tr.span_window("solve"))
    assert s["window_s"] == pytest.approx(50e-9)
    assert s["busy_s"] == pytest.approx(35e-9)
    ops = dict((n, t) for n, t in s["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 20e-9, "fusion.2": 15e-9,
                                 "copy": 5e-9})
    # longest gap first, each labelled by the innermost span at its middle
    assert s["breakdown"]["idle_gaps"] == [
        ["between_solves", pytest.approx(10e-9)], ["solve", pytest.approx(5e-9)]]
    assert T.label(26, tr.spans) == "between_solves"
    assert T.label(99, tr.spans) == "none"


def test_op_times_are_self_times():
    # a while op spanning its body's ops on the same line
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 90),
           ("copy.1", 50, 60), ("fusion.1", 95, 99)]
    own = {(n, s): t for n, s, _, t in T.self_ns(ops)}
    assert own == {("while.1", 0): 26, ("fusion.1", 10): 20,
                   ("fusion.2", 40): 40, ("copy.1", 50): 10, ("fusion.1", 95): 4}
    top = T.top_ops(ops, 0, 100)
    assert [n for n, _ in top] == ["fusion.2", "while.1", "fusion.1", "copy.1"]
    assert [t for _, t in top] == pytest.approx([40e-9, 26e-9, 24e-9, 10e-9])


def test_busy_is_averaged_over_devices():
    tr = _toy()
    tr.ops["/device:TPU:1"] = [("fusion.1", 0, 50)]
    assert T.device_summary(tr, (0, 50))["busy_s"] == pytest.approx(42.5e-9)


def test_no_device_ops_is_an_error():
    with pytest.raises(RuntimeError, match="no device operations"):
        T.device_summary(T.Trace(), (0, 1))


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: two annotated solves, each a small
    program then ``probe_spmv``, and three chained ``probe_spmv`` calls."""
    raw = json.loads((DATA / "trace_v5e.json").read_text())
    tr = T.Trace(ops={"/device:TPU:0": [tuple(e) for e in raw["ops"]]},
                 modules={"/device:TPU:0": [tuple(e) for e in raw["modules"]]},
                 spans=[tuple(e) for e in raw["spans"]])
    window = tr.span_window("solve")
    s = T.device_summary(tr, window)
    assert 0 < s["busy_s"] <= s["window_s"]
    idle = sum(g for _, g in s["breakdown"]["idle_gaps"])
    assert idle <= s["window_s"] - s["busy_s"] + 1e-12
    assert {lbl for lbl, _ in s["breakdown"]["idle_gaps"]} <= set(T.SPANS) | {"none"}
    probe = T.module_seconds(tr, "probe_spmv")
    assert len(probe) == 5
    assert max(probe) / min(probe) < 1.01  # one program, one time
    assert T.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.3"
