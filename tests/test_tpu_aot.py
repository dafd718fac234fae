"""Compile for a TPU v5e ahead of time, with no chip present.

The installed libtpu compiles for a DESCRIBED topology under
``JAX_PLATFORMS=cpu``: ``get_topology_desc("tpu", "v5e:2x2")`` gives four
abstract ``TPU v5 lite`` devices, and lowering against shardings on them
runs the real TPU compiler, Mosaic included, and reports memory. So a
kernel the compiler refuses, or a program that does not fit the chip's
HBM, fails here on the CPU instead of costing chip time.

The shapes are chip_smoke.py's, so what this file accepts is what the
smoke then runs. The kernels pick interpret mode from the one predicate
in ``ops/backend.py``; compiling FOR the TPU from a CPU process is the
one place that answer is wrong, so the fixture overrides it.
"""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import chip_smoke
from ray_tpu.models import (
    TransformerConfig,
    decode_step,
    init_kv_cache,
    init_params,
    param_specs,
    prefill_chunk,
)
from ray_tpu.ops import backend, flash_attention, flash_attention_grouped
from ray_tpu.ops.fused import rms_norm_fused
from ray_tpu.parallel.sharding import ShardingRules, kv_cache_specs

fa = importlib.import_module("ray_tpu.ops.flash_attention")

HBM_BYTES = 15.75 * 2 ** 30   # what the compiler allows of a v5e's 16 GB


@pytest.fixture(scope="module")
def tpu():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no ahead-of-time TPU compiler here: {exc!r}")
    assert [d.device_kind for d in topo.devices] == ["TPU v5 lite"] * 4
    return topo.devices


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    monkeypatch.setattr(backend, "on_cpu", lambda: False)


def _abstract(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding,
    or a matching tree of them)."""
    if not isinstance(sharding, (dict, list, tuple)):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sharding)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _qkv(dev, s, d, dtype=jnp.bfloat16, heads=4, kv_heads=None):
    one = SingleDeviceSharding(dev)
    q = jax.ShapeDtypeStruct((1, heads, s, d), dtype, sharding=one)
    kv = jax.ShapeDtypeStruct((1, kv_heads or heads, s, d), dtype,
                              sharding=one)
    return q, kv, kv


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: flash_attention(*a).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1024, 4096])
def test_flash_kernels_compile(tpu, s, d):
    """Forward, both backward kernels and the grouped forward compile at
    the sizes the smoke's numerics phase then runs."""
    assert _kernel_calls(
        jax.jit(flash_attention).lower(*_qkv(tpu[0], s, d)).compile()) == 1
    assert _kernel_calls(
        jax.jit(_flash_grad).lower(*_qkv(tpu[0], s, d)).compile()) == 3
    assert _kernel_calls(jax.jit(flash_attention_grouped).lower(
        *_qkv(tpu[0], s, d, heads=8, kv_heads=2)).compile()) == 1


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, loop and kernel bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


@pytest.mark.parametrize("s,d", [(1024, 64), (1024, 128), (4096, 64),
                                 (4096, 128), (12288, 128)])
def test_flash_backward_is_three_named_kernels_and_no_tile_transpose(
        tpu, s, d):
    """The gradient is the forward, dq and dk/dv kernels under their
    names, up to the VMEM guard's edge, and the dk/dv kernel (k-major
    score tiles) transposes no [block, block] operand."""
    args = _qkv(tpu[0], s, d)
    text = jax.jit(_flash_grad).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"%{name}." in text or f"%{name} " in text, name
    calls = {eqn.params["name"]: eqn.params["jaxpr"]
             for eqn in _eqns(jax.make_jaxpr(_flash_grad)(*args).jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    block = fa._auto_block(s)
    dkv = [(eqn.primitive.name, [v.aval.shape for v in eqn.invars
                                 if hasattr(v.aval, "shape")])
           for eqn in _eqns(calls["flash_bwd_dkv"])]
    assert any(name == "dot_general" for name, _ in dkv)
    assert not [shapes for name, shapes in dkv
                if name == "transpose" and (block, block) in shapes]


@pytest.mark.parametrize("s", [64, 192])
def test_flash_guard_keeps_unaligned_blocks_off_the_compiler(tpu, s):
    """A block that is not a multiple of 128 lanes is refused by Mosaic
    (the log-sum-exp store); the guard sends it down the dense path."""
    block = fa._auto_block(s)
    assert block % 128 and not fa.use_flash(s, s, 64, jnp.bfloat16)
    for fn, args in ((flash_attention, _qkv(tpu[0], s, 64)),
                     (_flash_grad, _qkv(tpu[0], s, 64)),
                     (flash_attention_grouped,
                      _qkv(tpu[0], s, 64, heads=8, kv_heads=2))):
        assert _kernel_calls(jax.jit(fn).lower(*args).compile()) == 0
    with pytest.raises(Exception, match="multiple of 128"):
        jax.jit(lambda q, k, v: fa._flash_core(
            q, k, v, True, 0.125, block, block, False)).lower(
            *_qkv(tpu[0], s, 64)).compile()


def test_flash_vmem_limit_is_a_limit(tpu):
    """The kernels keep whole per-head arrays in VMEM. The guard's
    3 MiB per-head array (head_dim 128, bf16: S = 12288) compiles,
    forward and backward; S = 16384 is refused by the compiler, and the
    guard never lets it get there."""
    assert fa.use_flash(12288, 12288, 128, jnp.bfloat16)
    args = _qkv(tpu[0], 12288, 128)
    assert _kernel_calls(jax.jit(_flash_grad).lower(*args).compile()) == 3
    assert not fa.use_flash(16384, 16384, 128, jnp.bfloat16)
    assert not fa.use_flash(8192, 8192, 128, jnp.float32)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(lambda q, k, v: fa._flash_core(
            q, k, v, True, 0.09, 512, 512, False)).lower(
            *_qkv(tpu[0], 16384, 128)).compile()
    # Past the limit the public op lowers to the dense form, no kernel.
    lowered = jax.jit(flash_attention).lower(*_qkv(tpu[0], 16384, 128))
    assert "tpu_custom_call" not in lowered.as_text()


def test_flash_kernels_compile_at_heads_of_64_and_8192_tokens(tpu):
    """The LFM2 cell's attention layer: 32 query heads of 64 over 8192
    tokens, forward and both backward kernels, under their names."""
    assert fa.use_flash(8192, 8192, 64, jnp.bfloat16)
    text = jax.jit(_flash_grad).lower(
        *_qkv(tpu[0], 8192, 64, heads=32)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"%{name}." in text or f"%{name} " in text, name


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)])
@pytest.mark.parametrize("rows", [8192, 32768])
def test_grouped_matmul_kernels_compile_under_their_names(tpu, rows, k, n):
    """The held experts' grouped products at the LFM2 cell's widths (8
    experts of 2048 x 1536, gate and up, and of 1536 x 2048, down), over
    every sorted pair of a layer and over a quarter of them: the product
    and its two gradients are the repo's two kernels at the tiles these
    shapes give (whole weights of a group, a
    whole output tile: more VMEM than the compiler grants unasked), each
    call under the program's name for it."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul, kernel_accepts
    from ray_tpu.util import profiling

    one = SingleDeviceSharding(tpu[0])
    assert kernel_accepts(rows, k, n)
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one)
    rhs = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)
    assert _kernel_calls(
        jax.jit(grouped_matmul).lower(lhs, rhs, sizes).compile()) == 1
    grad = jax.jit(jax.grad(lambda a, b, n: grouped_matmul(a, b, n).astype(
        jnp.float32).sum(), argnums=(0, 1)))
    text = grad.lower(lhs, rhs, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == 2     # a sum needs no forward
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    named = sorted(m for line in calls for m in ("moe_gmm", "moe_tgmm")
                   if re.search(r"(?<![\w.])" + m + r"(?![\w.])", line))
    assert named == ["moe_gmm", "moe_tgmm"]
    assert set(named) <= set(profiling.KERNELS)
    # XLA's own expansion of ragged_dot would have dropped the name
    assert "ragged-dot" not in text


@pytest.mark.parametrize("rows", [8192, 32768])
def test_moe_row_passes_compile_under_their_names(tpu, rows):
    """The held experts' passes over the sorted rows at the LFM2 cell's
    widths, over every sorted pair of a layer and over a quarter of them:
    the gather and the scatter-add are loops with a trip count read on the
    device, the row-wise pass one kernel with such a grid, each under the
    program's name for it, forward and transposed."""
    from ray_tpu.ops import moe_rows
    from ray_tpu.parallel.moe import _swiglu_rows
    from ray_tpu.util import profiling

    one = SingleDeviceSharding(tpu[0])
    tokens = rows // 4
    assert moe_rows.rows_accept(rows, moe_rows.ROW_TILE, 2048, 1536)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def layer(h, token, n, g, u):
        xs = moe_rows.gather_rows(h, token, n)
        a = moe_rows.map_rows(_swiglu_rows, n, g, u)
        out = moe_rows.scatter_add_rows(xs.astype(jnp.float32), token, n,
                                        tokens)
        return out.sum() + a.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(layer, argnums=(0, 3, 4))).lower(
        shape((tokens, 2048), jnp.bfloat16), shape((rows,), jnp.int32),
        shape((), jnp.int32), shape((rows, 1536), jnp.bfloat16),
        shape((rows, 1536), jnp.bfloat16)).compile().as_text()
    assert "conditional(" not in text
    assert len(re.findall(r"\bwhile\(", text)) == 2     # a sum needs no forward
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("moe_map_rows" in line for line in calls) == 1
    for name in ("moe_gather_rows", "moe_scatter_rows", "moe_map_rows"):
        assert name in profiling.KERNELS
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name


# The three expert cells' routers, (T, D, E, what ``route`` is told), each
# holding experts 0-7, and the sorts the compiled forward and backward of
# ``route`` held while it fetched its gates by index (``lax.top_k`` is a
# row sort on the v5e, the groups' two more; then the pairs' one).
CELLS_ROUTERS = {
    "lfm2": (8192, 2048, 64, 2, dict(
        k=4, score="sigmoid", norm_topk=True, scale=1.0)),
    "ling3": (4096, 2560, 512, 4, dict(
        k=8, score="sigmoid", norm_topk=True, scale=2.5, n_group=8,
        topk_group=4)),
    "nemotron3": (4096, 4096, 512, 2, dict(
        k=22, score="sigmoid", norm_topk=True, scale=5.0,
        keep_input=False)),
}


@pytest.mark.parametrize("cell", list(CELLS_ROUTERS))
def test_route_fetches_and_puts_nothing_by_index(tpu, cell):
    """``route`` alone through the v5e's compiler at a cell's size, forward
    and gradient in one program: no ``gather`` or ``scatter`` as large as
    the T x k pairs is left (the compiler moves one 4-byte scalar every
    8-12 ns through those: 1 to 3 ms a layer went there until PR 42), and
    the backward pass costs one sort more, the one that undoes the pairs'."""
    from ray_tpu.parallel import moe

    T, D, E, sorts_before, kw = CELLS_ROUTERS[cell]
    one = SingleDeviceSharding(tpu[0])
    rows = T * min(kw["k"], 8)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def weighed(h, router, bias, weights):
        r = moe.route(h, router, bias, experts_held=tuple(range(8)), **kw)
        return jnp.sum(jnp.where(r.held, r.gate, 0) * weights), r

    text = jax.jit(jax.value_and_grad(
        weighed, argnums=(0, 1), has_aux=True)).lower(
            shape((T, D), jnp.bfloat16), shape((D, E), jnp.float32),
            shape((E,), jnp.float32), shape((rows,), jnp.float32)
        ).compile().as_text()
    by_index = re.findall(r"= \(?\w+\[([\d,]*)\][^=]*? (gather|scatter)\(", text)
    for dims, op in by_index:
        size = int(np.prod([int(d) for d in dims.split(",") if d]))
        assert size < T * kw["k"], (op, dims)
    assert len(re.findall(r" sort\(", text)) == sorts_before + 1


def test_rms_norm_fused_compiles(tpu):
    one = SingleDeviceSharding(tpu[0])
    x = jax.ShapeDtypeStruct((8, 1024, 2048), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one)
    assert _kernel_calls(jax.jit(rms_norm_fused).lower(x, w).compile()) == 1


# ------------------------------------------------- serving at smoke widths
def _serve_programs(sharding_of, tp_mesh=None, num_blocks=None):
    """Abstract (params, cache) of the smoke's serving configuration and
    jitted prefill/decode as the engine builds them."""
    cfg = TransformerConfig(dtype=jnp.bfloat16, **chip_smoke.SERVE_MODEL)
    eng = dict(chip_smoke.SERVE_ENGINE)
    if num_blocks:
        eng["num_blocks"] = num_blocks
    rules = ShardingRules() if tp_mesh is not None else None
    params = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        init_kv_cache, cfg, eng["num_blocks"], eng["block_size"]))
    if tp_mesh is None:
        p_sh = c_sh = sharding_of
    else:
        p_sh = jax.tree.map(lambda s: NamedSharding(tp_mesh, s),
                            param_specs(cfg, rules),
                            is_leaf=lambda s: isinstance(s, P))
        c_sh = {k: NamedSharding(tp_mesh, s)
                for k, s in kv_cache_specs(rules).items()}
    kw = dict(mesh=tp_mesh, rules=rules)
    return (cfg, _abstract(params, p_sh), _abstract(cache, c_sh),
            jax.jit(functools.partial(prefill_chunk, cfg, **kw),
                    donate_argnums=(1,)),
            jax.jit(functools.partial(decode_step, cfg, **kw),
                    donate_argnums=(1,)))


def _ints(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _fits(compiled) -> float:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2 ** 30:.2f} GiB does not fit a v5e"
    return used


# ``_fits``' bytes a train step may take. The smoke's and Mistral's: their
# steps' at the parent of PR 40, whose layer scan ran over the float32
# masters, kept a second bfloat16 copy of every layer's weights for the
# backward pass and handed back float32 gradient stacks (compiles in this
# sandbox at db2cca6; since then the scan runs over one bfloat16 stack,
# 11.26 and 14.42 GiB): a step that passes them holds such a copy again.
# The expert cells': what their steps compile to since PR 42, whose ``route``
# keeps the [T, k] experts and the pairs' places for the backward pass and
# nothing of [T, k, E] (12.68 and 12.76 GiB; 2.3 and 8.4 MB under PR 40's).
# Ling's since PR 44, whose six KDA layers keep their chunks' inverses and
# states in place of a second forward (12.76 -> 13.46 GiB; 14.19 with the
# scan's operands, the scores and the right-hand sides kept as well).
# Since PR 46 the table's gradient is summed in float32 zeros where bf16 ones
# were: LFM2's [8192, 2048] 0.1 MB more (its tied table's rows are still
# read off the head's bf16 copy), Ling's 0.6 MB
# more (its 2560 columns summed as 2048 + 512 and joined, where the backward
# pass has spent its residuals), Mistral's the same to the byte.
STEP_BYTES = {
    "smoke": 12_106_853_888,                        # 11.28 GiB
    "mistral7b-train.seq4k": 16_358_345_216,        # 15.23 GiB
    "lfm2-24b-a2b-train.seq8k": 13_613_932_032,     # 12.68 GiB
    "ling3-flash-train.seq4k": 14_457_615_360,      # 13.46 GiB
}


def test_serving_programs_fit_one_chip(tpu):
    """decode_step at the smoke's full batch and prefill_chunk at its
    full token budget, with its pool, compile and fit 16 GB. The next
    pool size up does not: the scan-carried pool is copied, not updated
    in place (a finding for a later perf issue, a limit for now)."""
    one = SingleDeviceSharding(tpu[0])
    cfg, params, cache, prefill, decode = _serve_programs(one)
    eng = chip_smoke.SERVE_ENGINE
    b = eng["max_num_seqs"]
    m = 4096 // eng["block_size"]
    _fits(decode.lower(params, cache, _ints((b,), one), _ints((b,), one),
                       _ints((b, m), one)).compile())
    c = eng["prefill_token_budget"]
    _fits(prefill.lower(params, cache, _ints((1, c), one), _ints((1,), one),
                        _ints((1,), one), _ints((1, m), one)).compile())
    _cfg, params, cache, _prefill, decode = _serve_programs(
        one, num_blocks=2 * eng["num_blocks"])
    with pytest.raises(Exception, match="memory space hbm"):
        decode.lower(params, cache, _ints((b,), one), _ints((b,), one),
                     _ints((b, m), one)).compile()


def test_tp4_decode_compiles_on_the_2x2_host(tpu):
    mesh = Mesh(np.asarray(tpu).reshape(1, 1, 1, 4, 1, 1),
                ("dp", "fsdp", "pp", "tp", "sp", "ep"))
    rep = NamedSharding(mesh, P())
    cfg, params, cache, _prefill, decode = _serve_programs(None, mesh)
    eng = chip_smoke.SERVE_ENGINE
    b, m = eng["max_num_seqs"], 4096 // eng["block_size"]
    used = _fits(decode.lower(params, cache, _ints((b,), rep),
                              _ints((b,), rep), _ints((b, m), rep)
                              ).compile())
    # Per chip: a quarter of the weights and of the pool, not all of it.
    assert used < 4 * chip_smoke._param_count(chip_smoke.SERVE_MODEL)


# ------------------------------------------------------------- train step
def test_train_step_compiles_with_the_flash_kernels(tpu):
    """The 201M train step of the smoke at 8 x 1024 holds the flash
    forward, dq and dk/dv kernels — the dense path does not stand in."""
    import optax

    cfg = TransformerConfig(dtype=jnp.bfloat16, **chip_smoke.TRAIN_MODEL)
    lowered = chip_smoke._lower_train_step(
        cfg, optax.adamw(3e-4), sharding=SingleDeviceSharding(tpu[0]))
    assert lowered.as_text().count("tpu_custom_call") == 3
    compiled = lowered.compile()
    assert _kernel_calls(compiled) == 3
    assert _fits(compiled) <= STEP_BYTES["smoke"]


def test_train_step_names_its_kernels_and_its_fusions(tpu):
    """What a profile of the step shows, read from the v5e-compiled text
    as ``perfbench/segments.py`` reads it: the three flash kernels under
    three names of the vocabulary, and nine in ten of the fusions under
    one model segment or, with names and no segment, the update."""
    import optax

    from perfbench import segments
    from ray_tpu.util import profiling

    cfg = TransformerConfig(dtype=jnp.bfloat16, **chip_smoke.TRAIN_MODEL)
    text = chip_smoke._lower_train_step(
        cfg, optax.adamw(3e-4),
        sharding=SingleDeviceSharding(tpu[0])).compile().as_text()
    table = segments.attribute(text, profiling.SEGMENTS, profiling.KERNELS)
    kernels = sorted(r["kernel"] for r in table.values() if r["kernel"])
    assert kernels == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert all(r["segment"] == "seg.attn_core" for r in table.values()
               if r["kernel"])
    fusions = [r["segment"] for name, r in table.items() if "fusion" in name]
    named = [s for s in fusions if s != segments.UNATTRIBUTED]
    assert len(fusions) > 50 and len(named) >= 0.9 * len(fusions), (
        len(named), len(fusions))
    # a uniform stack: every segment but a layer pattern's
    pattern = {"seg.conv", "seg.moe_route", "seg.moe_experts",
               "seg.kda_proj", "seg.kda_core", "seg.moe_shared",
               "seg.mamba_proj", "seg.mamba_core", "seg.moe_latent",
               "seg.mtp"}
    assert set(profiling.SEGMENTS) - pattern <= {
        r["segment"] for r in table.values()}


def _lower_cell_step(dev, workload):
    """A training cell's AdamW step as its family builds it, at the cell's
    size, lowered for ``dev``."""
    from perfbench import harness

    cell = harness.load_cell(workload)
    family = harness.family(cell["paths"], cell["config"]["family"])
    step, init = family.build_step(cell["config"])
    params = jax.eval_shape(lambda: family.make_params(
        harness.run_model(cell["config"]), 0))
    args = (params, jax.eval_shape(init, params),
            jax.eval_shape(lambda: harness.seed_key(0)))
    return step.lower(*_abstract(args, SingleDeviceSharding(dev)), 0)


def _lower_lfm2_step(dev):
    return _lower_cell_step(dev, "lfm2-24b-a2b-train.seq8k")


def test_the_mistral_train_step_compiles_with_room(tpu):
    """The cell nearest the chip's memory (704.6 M parameters, 3 layers are
    refused): its step through the v5e's compiler, eight seconds, takes no
    more than at the parent."""
    workload = "mistral7b-train.seq4k"
    compiled = _lower_cell_step(tpu[0], workload).compile()
    assert _fits(compiled) <= STEP_BYTES[workload]


MOE_NAMES = ("moe_gmm", "moe_tgmm", "moe_gather_rows", "moe_map_rows",
             "moe_scatter_rows")


def test_the_lfm2_train_step_is_one_program_whatever_the_routing(tpu):
    """Lowered for the v5e at the cell's size, the step holds the grouped
    kernels and the row passes under their names and no branch: the
    expert layer is the same instructions from no pair routed here to all
    of them. (Lowered only: the compile, a minute of every core, is the
    slow test below.)"""
    text = _lower_lfm2_step(tpu[0]).as_text(debug_info=True)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.while") >= 4      # scans and row loops
    for name in MOE_NAMES + ("flash_fwd",):
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name


@pytest.mark.slow
def test_the_lfm2_train_step_compiles_and_fits_the_chip(tpu):
    """The same step through the v5e's compiler: it fits the chip and no
    ``conditional`` came of it. Slow-marked because the compile keeps
    every core of this sandbox busy for most of a minute, which timing
    tests in the other workers do not survive; run it by hand before
    spending chip time on the cell
    (``pytest tests/test_tpu_aot.py -m slow``)."""
    compiled = _lower_lfm2_step(tpu[0]).compile()
    assert _fits(compiled) <= STEP_BYTES["lfm2-24b-a2b-train.seq8k"]
    text = compiled.as_text()
    assert "conditional(" not in text
    for name in MOE_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name


def _flash_call_widths(lowered_text):
    """The last axis of every bf16 ``[heads, S, width]`` operand and result
    of the step's flash kernels."""
    calls = [line for line in lowered_text.splitlines()
             if "tpu_custom_call" in line and "flash_" in line]
    assert len(calls) == 3 or len(calls) % 3 == 0 and calls
    return {int(w) for line in calls
            for w in re.findall(r"tensor<\d+x\d+x(\d+)xbf16>", line)}


@pytest.mark.parametrize("workload,width", [
    ("mistral7b-train.seq4k", 128), ("lfm2-24b-a2b-train.seq8k", 64),
    ("nemotron3-super-train.seq4k", 128)])
def test_the_other_cells_flash_kernels_keep_their_one_width(tpu, workload,
                                                            width):
    """A value width of its own is the Ling cell's alone: in the Mistral and
    LFM2 steps every operand of the three kernels is as wide as a head, as
    at the parent (whose lowered steps these are, text for text outside the
    kernels' source locations: PERF.md section 6, PR 39)."""
    text = _lower_cell_step(tpu[0], workload).as_text(debug_info=True)
    assert _flash_call_widths(text) == {width}


LING_NAMES = MOE_NAMES + ("seg.kda_core", "flash_fwd", "flash_bwd_dq",
                          "flash_bwd_dkv")


def test_the_ling_train_step_holds_its_kernels_at_two_widths(tpu):
    """Lowered for the v5e at the cell's size: the KDA scan under its segment,
    the held experts' passes, and the three flash kernels on operands 192
    wide for queries and keys and 128 for values, none padded; one
    inversion of the chunks' triangles for each run of KDA layers (three,
    two and one), so none made again or solved on a backward path (with
    the forward made twice: twelve). (Lowered only: the compile is the slow
    test below.)"""
    text = _lower_cell_step(tpu[0], "ling3-flash-train.seq4k").as_text(
        debug_info=True)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.triangular_solve") == 3
    for name in LING_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name
    assert _flash_call_widths(text) == {192, 128}


@pytest.mark.slow
def test_the_ling_train_step_compiles_and_fits_the_chip(tpu):
    """The Ling cell's step through the v5e's compiler: 577.9 M parameters
    with their AdamW state and a 1 x 4096 step's temporaries fit the chip.
    Slow-marked as the LFM2 step's compile above, for the same reason."""
    compiled = _lower_cell_step(tpu[0], "ling3-flash-train.seq4k").compile()
    assert _fits(compiled) <= STEP_BYTES["ling3-flash-train.seq4k"]
    text = compiled.as_text()
    assert "conditional(" not in text
    # a run of KDA layers: one inversion, on the forward path, and the
    # chunks' loop once forward and once backward
    core = [line for line in text.splitlines() if "seg.kda_core" in line]
    inversions = [line for line in core
                  if "InvertDiagBlocksLowerTriangular" in line]
    assert len(inversions) == 3
    assert not any("transpose(jvp" in line for line in inversions)
    assert sum(" while(" in line for line in core) == 6
    for name in LING_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name


NEMOTRON = "nemotron3-super-train.seq4k"
NEMOTRON_NAMES = MOE_NAMES + ("seg.mamba_proj", "seg.mamba_core",
                              "seg.moe_latent", "seg.moe_shared",
                              "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# ``_fits``' bytes of the Nemotron-H cell's step since PR 42 (14.31 GiB, 12
# MB under PR 41's; 17.53 GiB with all T x k sorted rows and the expert
# layers' float32 copies of the stream kept; a [4096, 22, 512] float32 kept
# in each of five layers would be 0.9 GB): a step that passes them keeps
# one again. 4.7 MB more since PR 46 (the table's float32 gradient).
NEMOTRON_STEP_BYTES = 15_370_102_784


def test_the_nemotron_train_step_is_two_scans_with_its_names(tpu):
    """Lowered for the v5e at the cell's size: the pattern EMEMEMEMEM* is
    one scan of five (expert, Mamba) units and one of the attention layer,
    not eleven layers written out; the Mamba scan, the latent's projections
    and the shared expert under their segments, the held experts' passes
    and the three flash kernels under their names, and no branch. (Lowered
    only: the compile is the slow test below.)"""
    text = _lower_cell_step(tpu[0], NEMOTRON).as_text(debug_info=True)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    for name in NEMOTRON_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name
    # each grouped kernel is traced once a scan body and pass, not once a
    # layer: five expert layers share one body
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "moe_gmm" in line]
    assert 0 < len(calls) <= 8, len(calls)
    sorted_rows = re.findall(r"tensor<(\d+)x2688xbf16>", text)
    # a token takes an expert once: 4096 x 8 held, not 4096 x 22, rows
    assert set(sorted_rows) == {"32768"}


@pytest.mark.slow
def test_the_nemotron_train_step_compiles_and_fits_the_chip(tpu):
    """The Nemotron-H cell's step through the v5e's compiler: 713.4 M
    parameters with their AdamW state and a 1 x 4096 step's temporaries fit
    the chip, in no more than PR 41 left them. Slow-marked as the LFM2
    step's compile above, for the same reason (half a minute of every
    core)."""
    compiled = _lower_cell_step(tpu[0], NEMOTRON).compile()
    assert _fits(compiled) <= NEMOTRON_STEP_BYTES
    text = compiled.as_text()
    assert "conditional(" not in text
    for name in NEMOTRON_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name


JOYAI = "joyai-flash-train.seq4k"
JOYAI_NAMES = MOE_NAMES + ("seg.mtp", "seg.moe_shared", "seg.mlp",
                           "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# ``_fits``' bytes of the JoyAI cell's step as PR 45 left it (15.12 GiB:
# arguments 7.60, temporaries 7.51): a step that passes them keeps more
# for the backward pass of its six latent-attention bodies than it did.
# 66 MB more since PR 46 (15.18 GiB): the module's look-up sums its rows
# into float32 [16160, 2048] where bf16 was, and that gradient waits out
# the stack's whole backward pass.
JOYAI_STEP_BYTES = 16_299_951_104


def test_the_joyai_train_step_holds_the_module_and_three_bodies(tpu):
    """Lowered for the v5e at the cell's size: the dense layer, one scan of
    the four expert layers and the multi-token-prediction module's layer,
    three bodies, each with the three flash kernels on operands 192 wide for
    queries and keys and 128 for values at all 32 heads; the module's under
    ``seg.mtp``; the held experts' passes under their names; no branch.
    (Lowered only: the compile is the slow test below.)"""
    text = _lower_cell_step(tpu[0], JOYAI).as_text(debug_info=True)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    for name in JOYAI_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name
    assert _flash_call_widths(text) == {192, 128}
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "flash_" in line]
    assert len(calls) == 9
    assert all("tensor<32x4096x192xbf16>" in line for line in calls)
    # no sorted row beyond the T x 8 pairs a token's 8 experts make
    assert set(re.findall(r"tensor<(\d+)x768xbf16>", text)) <= {
        "32768", "2048", "4096"}


@pytest.mark.slow
def test_the_joyai_train_step_compiles_and_fits_the_chip(tpu):
    """The JoyAI cell's step through the v5e's compiler: 680.4 M parameters
    with their AdamW state and a 1 x 4096 step's temporaries (six bodies of
    latent attention at 32 heads, two head passes) fit the chip, in no more
    than PR 45 left them. Slow-marked as the LFM2 step's compile above, for
    the same reason (a minute of every core)."""
    compiled = _lower_cell_step(tpu[0], JOYAI).compile()
    assert _fits(compiled) <= JOYAI_STEP_BYTES
    text = compiled.as_text()
    assert "conditional(" not in text
    for name in JOYAI_NAMES:
        assert re.search(r"(?<![\w.])" + name + r"(?![\w.])", text), name
    # the module's kernels carry its segment and their own names
    assert any("seg.mtp" in line and "flash_fwd" in line
               for line in text.splitlines())
