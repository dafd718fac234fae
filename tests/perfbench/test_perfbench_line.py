"""A tiny CPU run of the driver prints the contract's last line.

The configurations are toys of the tests' own (``data/``), never a
benchmark configuration. ``allow_cpu`` skips the harness's look for a
chip and drives the rest of a run; a CPU line says so in ``device``, and
the command itself refuses to measure without an accelerator.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from perfbench import harness, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "data", "manifest.json")
CELLS = ["tiny-train.tiny-steps"]


with open(os.path.join(HERE, "data", "recorded_trace.json")) as _f:
    RECORDED = json.load(_f)


def _recorded_planes(_trace_dir):
    """The profiler on the CPU writes no device plane: a traced line is
    built from the small recorded chip trace kept beside the tests."""
    return {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
            for p, lines in RECORDED["planes"].items()}


@pytest.fixture(scope="module")
def lines(request):
    cache = {}

    def get(cell, trace):
        if (cell, trace) not in cache:
            loaded = harness.load_cell(cell, MANIFEST)
            # A CPU has no trace of a device and no peak: for the shape of
            # a traced line alone, the test lends it the recorded trace with
            # that trace's own window, and the v5e's row of the table.
            real, real_peak = trace_reduce.load, harness.peak
            real_reduce = trace_reduce.reduce
            trace_reduce.load = _recorded_planes
            trace_reduce.reduce = lambda planes, _window_s: real_reduce(
                planes, RECORDED["span_ns"] / 1e9)
            harness.peak = lambda kind: real_peak("TPU v5 lite")
            try:
                cache[cell, trace] = (loaded, run.run_cell(
                    loaded, 2 ** 31 + 77, 1.0, trace, time.perf_counter(),
                    allow_cpu=True))
            finally:
                trace_reduce.load, harness.peak = real, real_peak
                trace_reduce.reduce = real_reduce
        return cache[cell, trace]

    return get


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_contracts_shape(lines, cell, trace):
    loaded, line = lines(cell, trace)
    line = json.loads(json.dumps(line))       # as it is printed and read
    assert harness.line_faults(line, loaded, trace) == []
    assert list(line)[:5] == list(harness.LINE_KEYS)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = loaded["per_layer"] if trace else loaded["end_to_end"]
    names = {m["name"] for m in wanted}
    if trace:
        assert set(line["metrics"]) <= names and line["metrics"]
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == names
        assert all(m["value"] > 0 for m in line["metrics"].values())
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "ok"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cpu_line_cannot_be_taken_for_a_device_result(lines, cell):
    _, line = lines(cell, False)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["kind"] == "cpu"
    with pytest.raises(SystemExit):
        harness.peak(line["device"]["kind"])


def test_line_faults_names_what_pr22_got_wrong(lines):
    loaded, line = lines("tiny-train.tiny-steps", True)
    bad = json.loads(json.dumps(line))
    del bad["device"]["busy_s"]
    assert any("busy_s" in f for f in harness.line_faults(bad, loaded, True))
    bad = json.loads(json.dumps(line))
    bad["device"]["busy_s"] = bad["device"]["window_s"] * 2
    assert harness.line_faults(bad, loaded, True)
    bad = json.loads(json.dumps(line))
    bad["metrics"]["train.step_ms"] = 3.0     # a bare number, no unit
    assert harness.line_faults(bad, loaded, True)
    bad = {k: v for k, v in line.items() if k != "device"}
    assert harness.line_faults(bad, loaded, True) == ["missing key device"]
    _, plain = lines("tiny-train.tiny-steps", False)
    bad = json.loads(json.dumps(plain))
    del bad["metrics"]["setup_s"]
    assert "metric setup_s is missing" in harness.line_faults(
        bad, loaded, False)


def test_the_command_refuses_to_measure_without_an_accelerator(tmp_path):
    """The command as the driver runs it, on this CPU-only machine: a
    non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py"),
         "--workload", "mistral7b-train.seq4k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith("{")]


def test_an_unknown_workload_is_an_error_not_a_default():
    with pytest.raises(SystemExit):
        harness.load_cell("mistral7b-train.no-such-mix")
