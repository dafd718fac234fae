"""The five ``setup.*`` readers and the two rules they share
(``perfbench/setup_log.py``) on a build log recorded from a traced CPU run
of the tests' own tiny cell (``data/recorded_build_log.json``: the log as
the readers saw it, fifteen records of set-up, the window, the join's
build of the step), and on variants of it made by hand. The program's log
is lent to the readers through ``ray_tpu.util.profiling.build_log``, the
one call they make of it."""

import copy
import json
import os
import re
import time

import pytest

from perfbench import harness, run, setup_log, trace_reduce
from ray_tpu.util import profiling
from test_perfbench_line import RECORDED as RECORDED_TRACE, _recorded_planes

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = harness.load_json(harness.MANIFEST)
PATHS = MANIFEST["paths"]
with open(os.path.join(HERE, "data", "recorded_build_log.json")) as _f:
    RECORDED = json.load(_f)
SECONDS = RECORDED["seconds"]
READERS = ["setup.step_trace_lower_s", "setup.step_load_s",
           "setup.programs_after_step", "setup.after_step_build_s",
           "setup.cache_hit_share"]
# Worked out by hand from the file: the step's record is 2 (0.231682 s
# traced and 0.208498 lowered against 0.094 of the runner-up, record 3;
# 0.984871 in its backend span); records 3 to 15 follow it, one after the other
# with gaps between, so their union is the sum of their own lengths;
# record 8 alone came out of the cache.
BY_HAND = {"setup.step_trace_lower_s": 0.231682 + 0.208498,
           "setup.step_load_s": 0.984871,
           "setup.programs_after_step": 13.0,
           "setup.cache_hit_share": 100.0 / 15}


def _records():
    return copy.deepcopy(RECORDED["records"])


def _read(monkeypatch, records, metric, seconds=SECONDS):
    monkeypatch.setattr(profiling, "build_log",
                        lambda since_seq=0: copy.deepcopy(records))
    return harness.reader(PATHS, metric)({"seconds": seconds})


def test_the_recorded_log_is_what_a_run_leaves():
    records = _records()
    assert [r["seq"] for r in records] == list(range(1, 17))
    assert [r["name"] for r in records][:3] == [
        "jit(init_fn)", "jit(step)", "jit(norms)"]
    assert records[15]["name"] == "jit(step)" and records[15]["rebuilt"] == 2
    assert all(r["t0"] <= r["t1"] for r in records)


def test_the_silence_rule_finds_the_window():
    records = _records()
    start, end = setup_log.window(records, SECONDS)
    assert (start, end) == (records[14]["t1"], records[15]["t0"])
    assert end - start == pytest.approx(3.046, abs=1e-3)
    # set-up's own longest silence is far under the window's share
    assert len(setup_log.union(records[:15])) == 15
    assert max(b - a for a, b in setup_log.silences(records[:15])) < 0.1
    assert setup_log.window(records, 3.5) is None
    assert setup_log.window(records[:15], SECONDS) is None
    assert setup_log.window([], SECONDS) is None


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_returns_the_value_worked_out_by_hand(
        monkeypatch, capfd, metric):
    records = _records()
    value = _read(monkeypatch, records, metric)
    if metric == "setup.after_step_build_s":
        want = sum(r["t1"] - r["t0"] for r in records[2:15])
        assert 2.1 < want < 2.2
    else:
        want = BY_HAND[metric]
    assert value == pytest.approx(want, abs=1e-9)
    said = capfd.readouterr().err
    # the helper names the silence it took, the step and the runner-up
    assert "the window is the silence of 3.046s" in said
    assert "(the readers' seconds 2.860) after record 15 of 16" in said
    assert "the step's record is 2 jit(step), 0.440s traced and lowered" \
        in said
    assert "the runner-up 3 jit(norms) 0.094s" in said
    assert f"perfbench set-up: {metric}" in said


@pytest.mark.parametrize("metric", READERS)
def test_a_build_inside_the_window_silences_every_reader(
        monkeypatch, capfd, metric):
    records = _records()
    middle = (records[14]["t1"] + records[15]["t0"]) / 2
    planted = dict(records[7], seq=16, t0=middle, t1=middle + 0.01)
    records[15]["seq"] = 17
    records.insert(15, planted)
    assert _read(monkeypatch, records, metric) is None
    said = capfd.readouterr().err
    assert "no silence of 0.9 x 2.860s among 17 records" in said
    assert "(the longest 1.523s)" in said
    assert "built inside the window" in said


@pytest.mark.parametrize("metric", READERS)
def test_an_earlier_silence_as_long_as_the_window_changes_nothing(
        monkeypatch, metric):
    """A balancing pass that builds nothing for as long as a window, before
    the step is built: the last silence is still the window."""
    records = _records()
    for r in records[1:]:
        r["t0"] += 3.5
        r["t1"] += 3.5
    assert records[1]["t0"] - records[0]["t1"] > 3.5
    plain = _read(monkeypatch, _records(), metric)
    assert _read(monkeypatch, records, metric) == pytest.approx(plain)


@pytest.mark.parametrize("metric", READERS)
def test_the_joins_records_after_the_window_are_not_counted(
        monkeypatch, metric):
    """The joins build the step again after the window, each time slower
    than set-up built it: neither becomes the step's record, a program
    after the step or a record of set-up."""
    records = _records()
    plain = _read(monkeypatch, records, metric)
    last = records[15]
    for n, name in enumerate(["jit(step)", "jit(load)"]):
        t0 = last["t1"] + 0.2 + 3.0 * n
        records.append(dict(last, seq=17 + n, name=name, t0=t0, t1=t0 + 2.5,
                            backend_s=2.0, cache="miss", rebuilt=3))
    assert _read(monkeypatch, records, metric) == plain


def test_the_steps_record_is_picked_and_named(monkeypatch, capfd):
    monkeypatch.setattr(profiling, "build_log",
                        lambda since_seq=0: _records())
    found = setup_log.set_up({"seconds": SECONDS})
    assert found["step"]["seq"] == 2 and found["step"]["name"] == "jit(step)"
    assert [r["seq"] for r in found["records"]] == list(range(1, 16))
    assert [r["seq"] for r in found["after"]] == list(range(3, 16))
    assert "the step's record is 2 jit(step)" in capfd.readouterr().err
    # from a warm cache the step's backend span shrinks under another
    # program's; from an empty one another program compiles for longer than
    # the step (as ``change_norms``' do on the chip): the step's record is
    # picked by what both runs pay alike
    for backend_s, cache, other in [(0.02, "hit", 0.2), (0.98, "miss", 11.3)]:
        varied = _records()
        varied[1]["backend_s"], varied[1]["cache"] = backend_s, cache
        varied[8]["backend_s"] = other
        monkeypatch.setattr(profiling, "build_log",
                            lambda since_seq=0, log=varied: log)
        assert setup_log.set_up({"seconds": SECONDS})["step"]["seq"] == 2


def test_a_program_older_than_its_log_gives_nothing_and_does_not_raise(
        monkeypatch, capfd):
    """The parent commit has no ``build_log``: every reader returns None in
    silence, and the line leaves the metrics out."""
    monkeypatch.delattr(profiling, "build_log")
    for metric in READERS:
        assert harness.reader(PATHS, metric)({"seconds": SECONDS}) is None
    assert capfd.readouterr().err == ""
    cell = {"paths": PATHS, "per_layer": [
        m for m in MANIFEST["per_layer"] if m["name"] in READERS]}
    assert len(cell["per_layer"]) == 5
    assert harness.read_layer_metrics(cell, {"seconds": SECONDS}) == {}


def test_the_manifest_names_the_five_where_no_toy_pins_the_cells_list():
    """Each of the five lists its cells, so that a later cell that reports
    ``setup_s`` is not refused over them. The Ling and Nemotron-H cells are
    not listed yet: ``test_perfbench_ling3.py`` and
    ``test_perfbench_nemotron.py`` hold each of those cells' metric names
    to its toy manifest's, and the ``benchmark`` PR that appends the five
    to the two toys appends the two cells here."""
    five = [m for m in MANIFEST["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in five] == READERS
    for m in five:
        assert m["layer"] == "set-up" and m["moves"] == "setup_s"
        assert m["workloads"][:2] == ["mistral7b-train.seq4k",
                                      "lfm2-24b-a2b-train.seq8k"]
    toy = harness.load_cell(
        "tiny-train.tiny-steps",
        os.path.join(HERE, "data", "manifest-setup.json"))
    assert [m["name"] for m in toy["per_layer"]][-5:] == READERS


def test_a_traced_run_of_the_toy_cell_prints_the_five(monkeypatch, capfd):
    """The driver to the line, live: the window the run just made is the
    log's last long silence although this process built before (other
    tests' programs are earlier records, so only the shape is held here),
    the driver's own count of builds inside the window reads 0, and the
    step is built once before the window and again by the join after it."""
    loaded = harness.load_cell(
        "tiny-train.tiny-steps",
        os.path.join(HERE, "data", "manifest-setup.json"))
    real_reduce, real_peak = trace_reduce.reduce, harness.peak
    monkeypatch.setattr(trace_reduce, "load", _recorded_planes)
    monkeypatch.setattr(
        trace_reduce, "reduce", lambda planes, _window_s: real_reduce(
            planes, RECORDED_TRACE["span_ns"] / 1e9))
    monkeypatch.setattr(harness, "peak",
                        lambda kind: real_peak("TPU v5 lite"))
    since = len(profiling.build_log()) and profiling.build_log()[-1]["seq"]
    line = run.run_cell(loaded, 2 ** 31 + 43, 1.0, True,
                        time.perf_counter(), allow_cpu=True)
    assert harness.line_faults(line, loaded, True) == []
    got = {k: v["value"] for k, v in line["metrics"].items()
           if k.startswith("setup.")}
    assert sorted(got) == sorted(READERS)
    assert got["setup.step_trace_lower_s"] > 0 and got["setup.step_load_s"] > 0
    assert got["setup.programs_after_step"] >= 13
    assert got["setup.after_step_build_s"] > 0
    assert 0 <= got["setup.cache_hit_share"] <= 100
    said = capfd.readouterr().err
    assert "compilations inside the window: 0" in said
    assert "perfbench set-up: the window is the silence of" in said
    last_of_setup = int(re.search(r"after record (\d+) of \d+", said).group(1))
    steps = [r["seq"] <= last_of_setup for r in profiling.build_log(since)
             if r["name"] == "jit(step)"]
    assert steps[:2] == [True, False]
