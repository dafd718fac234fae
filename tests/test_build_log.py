"""The program's build log (``ray_tpu/ops/backend.py``): one record per
program built, fed by ``jax.monitoring``'s listeners alone. The cases that
need an order of events no real build gives feed a log of their own by
hand, as JAX would; the rest build real programs and read the process's
log through ``ray_tpu.util.profiling.build_log``."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import backend
from ray_tpu.util import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, BACKEND = backend._TRACE, backend._LOWER, backend._BACKEND
FIELDS = ["seq", "name", "t0", "t1", "trace_s", "lower_s", "backend_s",
          "cache", "retrieval_s", "thread"]

ONE_BUILD = """
import json, sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from ray_tpu.util import profiling
from ray_tpu.ops.backend import device_info
jax.jit(lambda x: (x @ x).sum())(jnp.ones((8, 8)))
print(json.dumps({"log": profiling.build_log(), "info": device_info()}))
""" % ROOT


def _since():
    return backend.device_info()["compilations"]


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """What two fresh processes, one after the other, say of one build on
    one cache directory that starts empty."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("build_log_cache")),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    said = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", ONE_BUILD], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return said


@pytest.mark.parametrize("run,cache", [(0, "miss"), (1, "hit")])
def test_a_build_misses_an_empty_cache_and_hits_a_warm_one(
        two_processes, run, cache):
    got = two_processes[run]
    (record,) = [r for r in got["log"] if r["name"] == "jit(<lambda>)"]
    assert list(record) == FIELDS
    assert record["trace_s"] > 0 and record["lower_s"] > 0
    assert record["t1"] - record["t0"] >= record["backend_s"] > 0
    assert record["cache"] == cache
    assert got["info"]["compilations"] == len(got["log"])
    if cache == "miss":
        assert record["retrieval_s"] == 0
        assert got["info"]["cache_hits"] == 0
        assert got["info"]["cache_misses"] == len(got["log"])
    else:
        assert 0 < record["retrieval_s"] <= record["backend_s"]
        assert got["info"]["cache_hits"] == len(got["log"])
        assert got["info"]["cache_misses"] == 0


def test_a_nested_trace_counts_once():
    """JAX reports the trace of an inner ``jit`` as a span inside the outer
    one's: by hand, exactly; and on a real build, whose three parts lie
    apart inside ``[t0, t1]``."""
    log = backend.BuildLog()
    log.on_span(TRACE, 1.0, 3.0, fun_name="inner_a")
    log.on_span(TRACE, 4.0, 6.0, fun_name="inner_b")
    log.on_span(TRACE, 0.0, 10.0, fun_name="outer")
    log.on_span(LOWER, 10.0, 11.0, fun_name="jit(outer)")
    log.on_span(BACKEND, 11.5, 13.5, fun_name="jit(outer)")
    (record,) = log.records()
    assert record["trace_s"] == 10.0 and record["lower_s"] == 1.0
    assert record["backend_s"] == 2.0
    assert (record["t0"], record["t1"]) == (0.0, 13.5)
    assert record["cache"] == "off" and "rebuilt" not in record

    def slow_to_trace(x):
        for _ in range(150):
            x = x * 1.0001 + 1.0
        return x

    inner_a = jax.jit(slow_to_trace)
    inner_b = jax.jit(lambda x: slow_to_trace(x) - 1.0)

    @jax.jit
    def build_log_outer(x):
        return inner_a(x).sum() + inner_b(x).sum()

    since = _since()
    build_log_outer(jnp.ones(7))
    record = [r for r in profiling.build_log(since)
              if r["name"] == "jit(build_log_outer)"]
    assert len(record) == 1
    r = record[0]
    assert r["trace_s"] > 0
    assert r["trace_s"] + r["lower_s"] + r["backend_s"] \
        <= r["t1"] - r["t0"] + 1e-6


def test_a_second_shape_of_one_function_is_a_rebuild():
    @jax.jit
    def build_log_churn(x):
        return x * 2

    since = _since()
    rebuilt = backend.device_info()["rebuilt"]
    build_log_churn(jnp.ones(3))
    build_log_churn(jnp.ones(3))            # the same program: no record
    build_log_churn(jnp.ones(5))
    mine = [r for r in profiling.build_log(since)
            if r["name"] == "jit(build_log_churn)"]
    assert [r.get("rebuilt") for r in mine] == [None, 2]
    assert mine[1]["seq"] > mine[0]["seq"]
    assert backend.device_info()["rebuilt"] >= rebuilt + 1


def test_two_threads_do_not_mix_their_pending_spans():
    """By hand: a span that thread A left waiting stays A's while B builds.
    Then for real: more builders than this sandbox gives a test cores, each
    building programs of its own name."""
    log = backend.BuildLog()
    a_traced, b_built = threading.Event(), threading.Event()

    def thread_a():
        log.on_span(TRACE, 0.0, 5.0, fun_name="a")
        a_traced.set()
        assert b_built.wait(10)
        log.on_span(BACKEND, 20.0, 21.0, fun_name="jit(a)")

    def thread_b():
        assert a_traced.wait(10)
        log.on_event("/jax/compilation_cache/compile_requests_use_cache")
        log.on_event("/jax/compilation_cache/cache_hits")
        log.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        0.25)
        log.on_span(BACKEND, 10.0, 11.0, fun_name="jit(b)")
        b_built.set()

    threads = [threading.Thread(target=f) for f in (thread_a, thread_b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    assert not any(t.is_alive() for t in threads)
    b, a = log.records()
    assert (b["name"], b["trace_s"], b["t0"], b["cache"],
            b["retrieval_s"]) == ("jit(b)", 0.0, 10.0, "hit", 0.25)
    assert (a["name"], a["trace_s"], a["t0"], a["cache"],
            a["retrieval_s"]) == ("jit(a)", 5.0, 0.0, "off", 0.0)
    assert a["thread"] != b["thread"]

    builders, each = 6, 4
    since = _since()
    failures = []

    def build(i):
        try:
            for n in range(each):
                def fn(x):
                    return x * (i + 2) + n

                fn.__name__ = f"build_log_thread_{i}"
                jax.jit(fn)(jnp.ones(3 + n)).block_until_ready()
        except Exception as e:              # reported below, in the test
            failures.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=build, args=(i,))
                   for i in range(builders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not failures
    mine = [r for r in profiling.build_log(since)
            if r["name"].startswith("jit(build_log_thread_")]
    assert len(mine) == builders * each
    by_thread = {}
    for r in mine:
        by_thread.setdefault(r["thread"], set()).add(r["name"])
        # its own trace and lowering, and nobody else's: they fit inside
        assert r["trace_s"] > 0 and r["lower_s"] > 0
        assert r["trace_s"] + r["lower_s"] + r["backend_s"] \
            <= r["t1"] - r["t0"] + 1e-6
    assert len(by_thread) == builders
    assert all(len(names) == 1 for names in by_thread.values())
    seqs = [r["seq"] for r in profiling.build_log(since)]
    assert seqs == sorted(set(seqs))


def test_the_log_is_bounded_and_its_totals_are_not():
    log = backend.BuildLog()
    for i in range(backend.LOG_RECORDS + 904):
        log.on_span(BACKEND, float(i), i + 0.5, fun_name=f"jit(f{i % 7})")
    kept = log.records()
    assert len(kept) == backend.LOG_RECORDS == 4096
    assert kept[0]["seq"] == 905 and kept[-1]["seq"] == 5000
    assert log.totals()["compilations"] == 5000
    assert log.totals()["compile_seconds"] == 2500.0
    assert log.totals()["rebuilt"] == 5000 - 7
    assert kept[-1]["rebuilt"] == 4999 // 7 + 1
    assert [r["seq"] for r in log.records(since_seq=4998)] == [4999, 5000]
    kept[0]["name"] = "a copy"
    assert log.records()[0]["name"] != "a copy"


def test_device_info_keeps_its_keys_and_counts_the_records_closed():
    before = backend.device_info()
    assert set(before) >= {
        "platform", "device_kind", "device_count", "jax_version",
        "visible_chips", "pid", "compilations", "compile_seconds",
        "device_bytes_in_use", "device_peak_bytes",
        "cache_hits", "cache_misses", "trace_lower_seconds", "rebuilt"}
    jax.jit(lambda x: x - 41)(jnp.ones(11))
    jax.jit(lambda x: x - 42)(jnp.ones(13))
    after = backend.device_info()
    new = profiling.build_log(since_seq=before["compilations"])
    assert after["compilations"] - before["compilations"] == len(new) >= 2
    assert after["compilations"] == new[-1]["seq"]
    assert after["compile_seconds"] >= before["compile_seconds"]
    assert after["trace_lower_seconds"] > before["trace_lower_seconds"]
    assert after["cache_hits"] + after["cache_misses"] \
        <= after["compilations"]
    assert not hasattr(backend, "_compiles")
