"""Device ownership on the process plane, checked without a chip.

A chip belongs to one process at a time, so the driver must never open
one, a worker granted chips must be started to see exactly those, every
other worker must stay on the CPU, and nothing may fall back to the CPU
silently. All of that shows in what the spawner does — the environment
it builds, the errors it raises — so none of it needs an accelerator.
"""

import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import compile_cache, tpu_chips
from ray_tpu._private.tpu_chips import ChipsBusyError, ChipTable, worker_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, env=None, timeout=120):
    full = dict(os.environ)
    full.update(env or {})
    for k in [k for k, v in full.items() if v is None]:
        del full[k]
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture
def two_chip_runtime():
    """A runtime that believes the host has two chips (explicit count:
    nothing is opened, so no chip is needed)."""
    ray_tpu.shutdown()
    worker = ray_tpu.init(num_cpus=2, num_tpus=2)
    yield worker
    ray_tpu.shutdown()


# ------------------------------------------------------------- the driver
def test_init_leaves_the_driver_without_a_jax_backend():
    out = _python(
        "import sys, ray_tpu\n"
        "assert 'jax' not in sys.modules, 'import ray_tpu imported jax'\n"
        "w = ray_tpu.init(num_cpus=1)\n"
        "bridge = sys.modules.get('jax._src.xla_bridge')\n"
        "print('backends', sorted(getattr(bridge, '_backends', {}) or {}))\n"
        "print('tpus', w.resource_pool.total.get('TPU', 0), w.chips.total)\n"
        "ray_tpu.shutdown()\n")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "backends []" in out.stdout
    assert "tpus 0 0" in out.stdout  # no device nodes in this sandbox


def test_chips_are_counted_from_device_nodes(monkeypatch, tmp_path):
    for n in ("0", "1", "2", "3", "vfio"):
        (tmp_path / n).touch()
    real_listdir = os.listdir
    monkeypatch.setattr(tpu_chips.glob, "glob", lambda pat: [])
    monkeypatch.setattr(
        tpu_chips.os, "listdir",
        lambda p: real_listdir(tmp_path) if p == "/dev/vfio"
        else real_listdir(p))
    assert tpu_chips.detect_num_chips() == 4


# ------------------------------------------------------ the spawn environment
def test_worker_env_by_grant():
    assert worker_env((), 4) == {"JAX_PLATFORMS": "cpu"}
    assert worker_env((2,), 4) == {
        "JAX_PLATFORMS": "tpu,cpu", "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1", "TPU_HOST_BOUNDS": "1,1,1"}
    # Every chip of the host: libtpu's own defaults, nothing to set.
    assert worker_env((0,), 1) == {"JAX_PLATFORMS": "tpu,cpu"}
    assert worker_env((0, 1, 2, 3), 4) == {"JAX_PLATFORMS": "tpu,cpu"}
    with pytest.raises(ValueError, match="one chip or all 4"):
        worker_env((0, 1), 4)   # libtpu refused a two-chip view


def test_actor_workers_are_spawned_by_their_grant(two_chip_runtime):
    @ray_tpu.remote
    class Plain:
        def env(self):
            return os.environ.get("JAX_PLATFORMS")

    @ray_tpu.remote(num_tpus=1)
    class OnChip:
        def env(self):
            return {k: os.environ.get(k) for k in (
                "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")}

    plain, chip_a, chip_b = Plain.remote(), OnChip.remote(), OnChip.remote()
    # The driver's own environment pins the CPU (conftest); a worker with
    # no TPU keeps that pin, one with a chip is started without it.
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert ray_tpu.get(plain.env.remote()) == "cpu"
    envs = ray_tpu.get([chip_a.env.remote(), chip_b.env.remote()])
    assert [e["JAX_PLATFORMS"] for e in envs] == ["tpu,cpu"] * 2
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == ["0", "1"]
    assert all(e["TPU_HOST_BOUNDS"] == "1,1,1" for e in envs)
    spawn_env = chip_a._runtime._proc.spawn_env
    assert spawn_env["JAX_PLATFORMS"] == "tpu,cpu"
    assert spawn_env["TPU_VISIBLE_CHIPS"] in ("0", "1")
    assert plain._runtime._proc.spawn_env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in plain._runtime._proc.spawn_env


def test_second_claimant_fails_naming_the_holder(two_chip_runtime):
    @ray_tpu.remote(num_tpus=2)
    class Both:
        def ping(self):
            return os.environ.get("TPU_VISIBLE_CHIPS")

    first = Both.remote()
    # Every chip of the host: no visibility variables at all.
    assert ray_tpu.get(first.ping.remote()) is None
    with pytest.raises(ChipsBusyError, match=r"held by.*actor Both"):
        Both.remote()
    assert two_chip_runtime.resource_pool.available().get("TPU") == 0.0
    ray_tpu.kill(first)
    # The chips come back once the holder's process is gone.
    assert set(two_chip_runtime.chips.holders().values()) == {None}
    assert ray_tpu.get(Both.remote().ping.remote()) is None


def test_chip_table_and_fractions():
    table = ChipTable(2)
    assert table.take(1, "a") == (0,) and table.take(1, "b") == (1,)
    with pytest.raises(ChipsBusyError, match="chip 0: a, chip 1: b"):
        table.take(1, "c")
    table.give_back((0,))
    assert table.take(1, "c") == (0,)
    with pytest.raises(ValueError, match="whole number of chips"):
        tpu_chips.chips_requested({"num_tpus": 0.5})
    assert tpu_chips.chips_requested({"resources": {"TPU": 2}}) == 2
    assert tpu_chips.chips_requested({"num_gpus": 1}) == 1


def test_tpu_task_gets_a_worker_of_its_own(two_chip_runtime):
    @ray_tpu.remote(num_tpus=1)
    def on_chip():
        return (os.getpid(), os.environ["JAX_PLATFORMS"],
                os.environ["TPU_VISIBLE_CHIPS"])

    @ray_tpu.remote
    def plain():
        return os.getpid(), os.environ["JAX_PLATFORMS"]

    pid, platforms, chip = ray_tpu.get(on_chip.remote())
    assert (platforms, chip) == ("tpu,cpu", "0")
    assert ray_tpu.get(plain.remote())[1] == "cpu"
    # The chip worker is retired with its task: an idle pooled process
    # that had opened a chip would keep every later claimant out.
    assert pid not in two_chip_runtime.worker_pool.pids()
    assert set(two_chip_runtime.chips.holders().values()) == {None}
    assert ray_tpu.get(on_chip.remote())[0] != pid


def test_a_chip_worker_never_falls_back_to_the_cpu(two_chip_runtime):
    """No TPU in this sandbox: a worker granted a chip must fail when it
    touches JAX, not run the model on the CPU of a child."""
    @ray_tpu.remote(num_tpus=1)
    class Model:
        def platform(self):
            import jax

            return jax.devices()[0].platform

    with pytest.raises(Exception, match="[Uu]nable to initialize backend"):
        ray_tpu.get(Model.remote().platform.remote(), timeout=120)


def test_a_chip_worker_retired_after_shutdown_leaves_the_driver_up():
    """A chip worker is retired by its task's thread; when that thread
    runs late, after ``shutdown`` has closed the store, the retirement's
    channel teardown must not take the driver down."""
    out = _python(
        "import os, time, ray_tpu\n"
        "from ray_tpu._private import worker_pool\n"
        "retire = worker_pool.WorkerProcess.shutdown\n"
        "def late(self, timeout=2.0):\n"
        "    time.sleep(1.0)\n"
        "    return retire(self, timeout)\n"
        "worker_pool.WorkerProcess.shutdown = late\n"
        "ray_tpu.init(num_cpus=1, num_tpus=1)\n"
        "on_chip = ray_tpu.remote(num_tpus=1)(lambda: os.getpid())\n"
        "assert ray_tpu.get(on_chip.remote()) != os.getpid()\n"
        "ray_tpu.shutdown()\n"
        "time.sleep(1.5)\n"
        "print('up')\n")
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    assert out.stdout.split() == ["up"]


def test_use_tpu_requests_a_chip_for_each_train_worker():
    from ray_tpu.train import ScalingConfig

    assert ScalingConfig().worker_resources() == {}
    assert ScalingConfig(use_tpu=True).worker_resources() == {"TPU": 1.0}
    assert ScalingConfig(
        use_tpu=True, resources_per_worker={"TPU": 4, "CPU": 2}
    ).worker_resources() == {"TPU": 4, "CPU": 2}


# ------------------------------------------------------ the compile cache
def test_compile_cache_rule(tmp_path):
    code = ("import os, ray_tpu\n"
            "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
            "import jax\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    given = _python(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert given.stdout.split() == [str(tmp_path)] * 2, given.stderr[-2000:]
    default = _python(code, {"JAX_COMPILATION_CACHE_DIR": None})
    assert default.stdout.split() == [os.path.join(ROOT, ".jax_cache")] * 2
    assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")


# -------------------------------------------------- who holds the model
def test_stats_and_train_context_name_the_device():
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerConfig
    from ray_tpu.train.session import TrainContext

    engine = InferenceEngine(EngineConfig(
        model=TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                dtype=jnp.float32),
        num_blocks=16, block_size=4))
    try:
        assert len(list(engine.generate([1, 2, 3], max_new_tokens=2))) == 2
        for info in (engine.stats(),
                     TrainContext(0, 1).get_device_info()):
            assert info["platform"] == "cpu"
            assert info["device_kind"] and info["device_count"] >= 1
            assert info["compilations"] >= 1
        assert engine.stats()["failed_requests"] == 0
    finally:
        engine.shutdown()


def test_engine_counts_a_request_failed_by_the_loop(monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import TransformerConfig

    engine = InferenceEngine(EngineConfig(
        model=TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                dtype=jnp.float32),
        num_blocks=16, block_size=4))

    def boom(*_a, **_k):
        raise RuntimeError("device said no")

    monkeypatch.setattr(engine, "_prefill_chunk", boom)
    try:
        with pytest.raises(RuntimeError, match="device said no"):
            list(engine.generate([1, 2, 3], max_new_tokens=2))
        stats = engine.stats()
        assert stats["failed_requests"] == 1
        assert "device said no" in stats["last_failure"]
    finally:
        engine.shutdown()


# ------------------------------------------------------------ chip_smoke
def test_chip_smoke_refuses_a_cpu():
    smoke = os.path.join(ROOT, "chip_smoke.py")
    pinned = subprocess.run(
        [sys.executable, smoke], env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert pinned.returncode != 0 and "no TPU" in pinned.stderr
    assert '"ok"' not in pinned.stdout
    # Not pinned either: JAX looks for a chip, finds none, and the
    # `devices` phase refuses the CPU it is left with.
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    found_none = subprocess.run([sys.executable, smoke], env=env,
                                capture_output=True, text=True, timeout=300)
    assert found_none.returncode != 0 and "no TPU" in found_none.stderr
    assert '"ok"' not in found_none.stdout


def test_chip_smoke_alone_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0 and '"ok"' not in out.stdout
