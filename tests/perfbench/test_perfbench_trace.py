"""trace_reduce on events counted by hand, and on a small recorded chip
trace kept beside the tests (``data/recorded_trace.json``: an excerpt of a
traced run of this benchmark on a TPU v5 lite, as plain tuples)."""

import json
import os

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _planes():
    # names as the chip's trace gives them: the HLO instruction's text
    kernel = ' = bf16[32,4096,128] custom-call(...), custom_call_target="tpu_custom_call"'
    ops = [("%while.3 = (s32[], bf16[2,4096]) while(...)", 0, 6 * MS),
           ("%fusion.1 = bf16[8] fusion(...)", 0, 4 * MS),           # nested
           ("%fusion.2 = bf16[8] fusion(...)", 4 * MS, 1 * MS),      # nested
           ("%closed_call.30" + kernel, 10 * MS, 5 * MS),
           ("%closed_call.31" + kernel, 15 * MS, 5 * MS),            # abuts
           ("%fusion.1 = bf16[8] fusion(...)", 40 * MS, 2 * MS)]
    host = [("PjitFunction(step)", 19 * MS, 3 * MS),
            ("np.asarray readback", 20 * MS, 19 * MS),
            ("sampling", 25 * MS, 10 * MS)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
                ("jit_step", 0, 42 * MS)], "Steps": [("0", 0, 42 * MS)]},
            "/host:CPU": {"main": host[:2], "worker": host[2:]},
            "/host:metadata": {}}


def test_busy_time_is_the_union_of_the_operations():
    planes = _planes()
    ops = tr.device_ops(planes)
    assert list(ops) == ["/device:TPU:0"]
    # [0,6) + [10,20) + [40,42) = 18 ms; the modules line is not counted
    assert tr.union(ops["/device:TPU:0"]) == [
        (0, 6 * MS), (10 * MS, 20 * MS), (40 * MS, 42 * MS)]
    assert tr.busy_seconds(ops["/device:TPU:0"]) == pytest.approx(0.018)
    got = tr.reduce(planes, window_s=0.05)
    assert got["busy_s"] == pytest.approx(0.018)
    assert got["window_s"] == 0.05 and got["devices_traced"] == 1


def test_kernel_time_and_top_operations_by_name():
    ops = tr.device_ops(_planes())
    assert tr.kernel_seconds(ops, ("tpu_custom_call",)) == pytest.approx(0.010)
    assert tr.kernel_seconds(ops, ("closed_call.31 ",)) == pytest.approx(0.005)
    assert tr.kernel_seconds(ops, ("no_such",)) == 0.0
    top = dict(tr.top_ops(ops))
    # names are cut to the instruction's own; a while keeps only the time
    # its nested operations leave: 6 - 4 - 1 = 1 ms
    assert top["fusion.1"] == pytest.approx(0.006)
    assert top["while.3"] == pytest.approx(0.001)
    assert top["closed_call.30"] == pytest.approx(0.005)
    assert tr.top_ops(ops)[0][0] == "fusion.1"
    assert len(tr.top_ops(ops, n=2)) == 2
    assert tr.short("%fusion.9 = f32[2]{0} fusion(f32[2] %p), kind=kLoop") \
        == "fusion.9"


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = tr.idle_gaps(_planes())
    # the 20 ms gap [20,40) is covered whole by the readback; the 4 ms gap
    # [6,10) by no host event
    assert gaps[0] == ["np.asarray readback", pytest.approx(0.020)]
    assert gaps[1] == ["no host event", pytest.approx(0.004)]
    assert len(gaps) == 2


def test_two_devices_are_averaged():
    planes = _planes()
    planes["/device:TPU:1"] = {"XLA Ops": [("%fusion.1 = x", 0, 2 * MS)]}
    got = tr.reduce(planes, window_s=0.05)
    assert got["devices_traced"] == 2
    assert got["busy_s"] == pytest.approx((0.018 + 0.002) / 2)


def test_busy_time_past_the_window_is_reported_and_the_line_refused():
    """A window taken wrongly, a line counted twice or a drifting clock
    must show: busy time is never cut to the window."""
    from perfbench import harness

    got = tr.reduce(_planes(), window_s=0.001)
    assert got["busy_s"] == pytest.approx(0.018) and got["window_s"] == 0.001
    line = {"correct": True, "attempted": 1, "failed": 0,
            "metrics": {"m": {"value": 1.0, "unit": "%"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": got["busy_s"],
                       "window_s": got["window_s"]},
            "checks": {}}
    cell = {"per_layer": [{"name": "m", "unit": "%"}], "end_to_end": []}
    faults = harness.line_faults(line, cell, True)
    assert len(faults) == 1 and "busy_s" in faults[0]
    line["device"]["window_s"] = 0.05
    assert harness.line_faults(line, cell, True) == []


def test_a_trace_with_no_device_plane_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.reduce({"/host:CPU": {"main": [("x", 0, 5)]}}, window_s=1.0)
    with pytest.raises(FileNotFoundError):
        tr.load(os.path.join(HERE, "data", "no-trace-here"))


def test_the_recorded_chip_trace_reduces_to_its_hand_count():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        rec = json.load(f)
    planes = {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
              for p, lines in rec["planes"].items()}
    got = tr.reduce(planes, window_s=rec["span_ns"] / 1e9)
    assert got["busy_s"] == pytest.approx(rec["hand"]["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    ops = tr.device_ops(planes)
    assert tr.kernel_seconds(ops, tuple(rec["hand"]["kernel_needles"])) \
        == pytest.approx(rec["hand"]["kernel_s"], rel=1e-9)
    assert got["device_ops"][0][0] == rec["hand"]["top_op"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
