"""The names the model gives its device work, checked on the CPU.

``ray_tpu/util/profiling.py`` holds the vocabulary (``SEGMENTS``,
``KERNELS``); ``models/transformer.py``, ``ops/paged_attention.py`` and
``ops/flash_attention.py`` write the ``jax.named_scope``s. XLA keeps a
scope as the ``op_name`` of every instruction it compiles, which is what a
profile shows and what ``perfbench/segments.py`` reads. These tests compile
tiny programs for the CPU and read the names back: a refactor that drops a
matmul out of every segment, or a scope out of the vocabulary, fails here.
"""

import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from perfbench import segments
from ray_tpu.models import (
    TransformerConfig,
    decode_step,
    init_kv_cache,
    init_params,
    loss_fn,
    prefill_chunk,
)
from ray_tpu.ops import flash_attention
from ray_tpu.util import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64, dtype=jnp.bfloat16)
# A layer pattern: a conv layer with the dense MLP, then an attention layer
# and a conv layer with routed experts, 2 of the router's 4 held.
PATTERN = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=64,
    head_dim=8, layer_types=("conv", "attention", "conv"), num_dense_layers=1,
    router_experts=4, experts_held=(1, 2), experts_per_token=2, moe_d_ff=16,
    router_score="sigmoid", norm_topk=True, expert_bias=True, qk_norm=True,
    norm_eps=1e-5, tie_embeddings=True, dtype=jnp.bfloat16)
# Another: a KDA layer with the dense MLP, then a KDA and an MLA layer with
# routed experts (the choice limited to 2 of 4 groups) and a shared expert.
HYBRID = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=64,
    head_dim=16, layer_types=("kda", "kda", "mla"), conv_kernel=4,
    kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    num_dense_layers=1, router_experts=16, experts_held=(1, 2),
    experts_per_token=4, moe_d_ff=16, router_score="sigmoid", norm_topk=True,
    expert_bias=True, router_groups=4, router_groups_kept=2, shared_d_ff=16,
    dtype=jnp.bfloat16)
# A third: layers that are a mixer or a feed-forward alone, an expert layer
# (two-matrix experts at a latent width, a shared expert) and a Mamba-2
# layer twice, then an attention layer without rope.
ALONE = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=5, n_heads=4, n_kv_heads=2,
    head_dim=8, layer_types=("none", "mamba", "none", "mamba", "attention"),
    layer_ffns=("moe", "none", "moe", "none", "none"), conv_kernel=4,
    rope=False, mamba_heads=4, mamba_head_dim=8, mamba_state=16,
    mamba_groups=2, mamba_chunk=8, router_experts=16, experts_held=(1, 2),
    experts_per_token=6, moe_d_ff=24, moe_latent=16, ffn_act="relu2",
    router_score="sigmoid", norm_topk=True, routed_scale=5.0,
    expert_bias=True, shared_d_ff=40, norm_eps=1e-5, dtype=jnp.bfloat16)
# A fourth: latent attention with a query latent and no gate in both
# layers, a dense MLP and then routed experts with a shared one, and a
# multi-token-prediction module behind the stack.
MODULE = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
    head_dim=16, layer_types=("mla", "mla"), kv_lora_rank=16, q_lora_rank=24,
    mla_gate=False, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    num_dense_layers=1, router_experts=16, experts_held=(1, 2),
    experts_per_token=4, moe_d_ff=16, router_score="sigmoid", norm_topk=True,
    expert_bias=True, shared_d_ff=16, mtp_depth=1, dtype=jnp.bfloat16)
# A fifth: the dense stack looped, its two layers run three times with
# sandwich norms, an exit gate after every pass and the exit objective.
LOOPED = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=64,
    total_ut_steps=3, sandwich_norm=True, dtype=jnp.bfloat16)
# The model's train programs, and the segments each one holds: a dense
# stack has no conv, KDA or expert layer, a pattern holds its own kinds',
# and the union is the vocabulary.
TRAIN = {"dense": CFG, "pattern": PATTERN, "hybrid": HYBRID, "alone": ALONE,
         "module": MODULE, "looped": LOOPED}
OF_A_LOOP = ("seg.exit",)
OF_A_MODULE = ("seg.mtp",)
OF_ALONE = ("seg.mamba_proj", "seg.mamba_core", "seg.moe_latent")
OF_A_HYBRID = ("seg.kda_proj", "seg.kda_core", "seg.moe_shared")
OF_A_PATTERN = ("seg.conv", "seg.moe_route", "seg.moe_experts") \
    + OF_A_HYBRID + OF_ALONE + OF_A_MODULE
SEGMENTS_OF = {
    "dense": tuple(s for s in profiling.SEGMENTS
                   if s not in OF_A_PATTERN + OF_A_LOOP),
    "pattern": tuple(s for s in profiling.SEGMENTS if s not in
                     OF_A_HYBRID + OF_ALONE + OF_A_MODULE + OF_A_LOOP),
    "hybrid": tuple(s for s in profiling.SEGMENTS if s not in
                    ("seg.conv",) + OF_ALONE + OF_A_MODULE + OF_A_LOOP),
    "alone": tuple(s for s in profiling.SEGMENTS if s not in (
        "seg.mlp", "seg.conv", "seg.kda_proj", "seg.kda_core")
        + OF_A_MODULE + OF_A_LOOP),
    "module": tuple(s for s in profiling.SEGMENTS if s not in (
        "seg.conv", "seg.kda_proj", "seg.kda_core") + OF_ALONE + OF_A_LOOP),
    "looped": tuple(s for s in profiling.SEGMENTS if s not in OF_A_PATTERN)}


def _params(cfg=CFG):
    return jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.PRNGKey(0))


def _tokens(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _paths(compiled_text):
    """(op_name path, is a matmul) of every instruction of a compiled
    program, fused ones included."""
    return [(path, instr.opcode in segments.MATMUL_OPCODES)
            for instrs in segments.parse(compiled_text).values()
            for instr in instrs for path in instr.paths]


def _segments_on(path):
    return set(re.findall(r"(?<![\w.])seg\.\w+", path))


def _outermost(path):
    """The segment a reader gives ``path`` to: the first on it."""
    return re.findall(r"(?<![\w.])seg\.\w+", path)[:1]


@functools.lru_cache(maxsize=None)
def _train_step(program):
    """(lowered, compiled text) of a value_and_grad + AdamW step of one of
    the model's train programs."""
    cfg, opt = TRAIN[program], optax.adamw(3e-4)

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = _params(cfg)
    lowered = jax.jit(step).lower(params, jax.eval_shape(opt.init, params),
                                  _tokens(2, 16), _tokens(2, 16))
    return lowered, lowered.compile().as_text()


@pytest.fixture(scope="module")
def train_step():
    return _train_step("dense")


@functools.lru_cache(maxsize=None)
def _gradient_text(program):
    cfg = TRAIN[program]
    return jax.jit(jax.grad(lambda p, t, y: loss_fn(cfg, p, t, y))).lower(
        _params(cfg), _tokens(2, 16), _tokens(2, 16)).compile().as_text()


@functools.lru_cache(maxsize=None)
def _gradient_paths(program):
    return _paths(_gradient_text(program))


@pytest.mark.parametrize("program,segment", [
    (program, segment) for program in TRAIN
    for segment in SEGMENTS_OF[program]])
def test_gradient_holds_each_segment_forward_and_backward(program, segment):
    mine = [p for p, _ in _gradient_paths(program)
            if segment in _segments_on(p)]
    assert any("jvp(" in p and "transpose(" not in p for p in mine), segment
    assert any("transpose(jvp(" in p for p in mine), segment


@pytest.mark.parametrize("program", sorted(TRAIN))
def test_the_tables_gradient_is_summed_under_its_segment(program):
    """``_embed``'s backward pass is a ``custom_vjp``'s and writes its scope
    itself: every sum of row cotangents into a ``[V, D]`` table lies in the
    backward pass under ``seg.embed`` (a module's second look-up under the
    module's, which is outermost), is made in float32, and none is made in
    the rows' type any more."""
    cfg = TRAIN[program]
    text = _gradient_text(program)
    table = rf"\[{cfg.vocab_size},{cfg.d_model}\]\S* scatter\("
    sums = re.findall(rf"= f32{table}.*?op_name=\"([^\"]*)\"", text)
    assert all(p.startswith("jit(<lambda>)/transpose(jvp(")
               and p.endswith("/seg.embed/scatter-add") for p in sums), sums
    assert sorted(_outermost(p)[0] for p in sums) == (
        ["seg.embed", "seg.mtp"] if cfg.mtp_depth else ["seg.embed"])
    assert not re.search(rf"= bf16{table}", text)


# Matmuls of the dense train step by segment: 4 + 2 + 3 a layer forward,
# twice that backward, the head's three; the layer body is lowered once, so
# the count does not grow with depth. A pattern lowers one body for each run
# of equal layers, and the CPU expands its grouped products (``ragged_dot``)
# in its own way, so there the rule is held and the count is not.
DENSE_MATMULS = {"seg.embed": 0, "seg.attn_proj": 12, "seg.attn_core": 6,
                 "seg.mlp": 9, "seg.head_loss": 3}


@pytest.mark.parametrize("program", sorted(TRAIN))
def test_every_matmul_of_the_train_step_lies_under_one_segment(program):
    lowered, text = _train_step(program)
    matmuls = [p for p, is_matmul in _paths(text) if is_matmul]
    for path in matmuls:
        # one segment; under a multi-token-prediction module, which is
        # outermost on its path, the module's and its layer's own
        assert len(_segments_on(path)) == 1 or (
            _outermost(path) == ["seg.mtp"]
            and len(_segments_on(path)) == 2), path
    by_segment = {s: sum([s] == _outermost(p) for p in matmuls)
                  for s in SEGMENTS_OF[program]}
    # The next-token loss's backward pass is a ``custom_vjp``'s rule
    # (``_logits_nll_grad``), which writes its segment itself, inside the
    # call's: its two products a head pass, x's cotangent and the weight's,
    # and a module's pass through the head under the module's, outermost.
    rule = [_outermost(p)[0] for p in matmuls
            if "transpose(" in p and "/seg.head_loss/seg.head_loss/" in p]
    assert sorted(rule) == ["seg.head_loss"] * 2 + (
        ["seg.mtp"] * 2 if program == "module" else []), rule
    if program == "dense":
        assert len(matmuls) == lowered.as_text().count(
            "stablehlo.dot_general") == 30
        assert by_segment == DENSE_MATMULS
    elif program == "pattern":
        # the conv operator's two products, the router's, the experts'
        assert all(by_segment[s] > 0 for s in by_segment if s != "seg.embed")
        assert by_segment["seg.conv"] == 12 and by_segment["seg.mlp"] == 9
        assert by_segment["seg.moe_route"] == 2 * 3    # one a layer, and back
    elif program == "alone":
        assert all(by_segment[s] > 0 for s in by_segment if s != "seg.embed")
        # one body for the two (expert, Mamba) units: a Mamba layer's three
        # products round its scan (``W_in`` in two parts, ``W_out``), the
        # latent's two and the shared expert's two, forward and twice
        # backward (the backward pass makes no product of them again)
        assert by_segment["seg.mamba_proj"] == 3 * 3
        assert by_segment["seg.moe_latent"] == 2 * 3
        assert by_segment["seg.moe_shared"] == 2 * 3
        assert by_segment["seg.moe_route"] == 3
        assert by_segment["seg.attn_proj"] == 4 * 3
    elif program == "module":
        assert all(by_segment[s] > 0 for s in by_segment if s != "seg.embed")
        # latent attention with a query latent: five products a layer
        # (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``), forward and
        # twice backward, in each of the stack's two runs; the module's own
        # five, its join, its head pass, its router, its shared expert's
        # three and its experts' lie under ``seg.mtp`` and nowhere else
        assert by_segment["seg.attn_proj"] == 2 * 5 * 3
        assert by_segment["seg.head_loss"] == 3
        assert by_segment["seg.moe_route"] == 3
        assert by_segment["seg.moe_shared"] == 9
        assert by_segment["seg.mtp"] >= (5 + 1 + 1 + 1 + 3) * 3
    elif program == "looped":
        # one body for all three passes: a layer's products forward, once
        # more where the backward pass makes the checkpointed layer again,
        # and twice backward; the head's likewise, once a pass; the exit
        # gate's forward and its weight's gradient
        assert by_segment == {"seg.embed": 0, "seg.attn_proj": 4 * 4,
                              "seg.attn_core": 2 * 4, "seg.mlp": 3 * 4,
                              "seg.head_loss": 4, "seg.exit": 2}
    else:
        assert all(by_segment[s] > 0 for s in by_segment if s != "seg.embed")
        # a KDA layer's seven products (six in, one out), forward and twice
        # backward, in each of the two runs of KDA layers: the backward pass
        # makes the elementwise chains between them anew and no product; an
        # MLA layer's five; a shared expert's three in each of two runs
        assert by_segment["seg.kda_proj"] == 2 * 7 * 3
        assert by_segment["seg.attn_proj"] == 5 * 3
        assert by_segment["seg.moe_shared"] == 2 * 9
        assert by_segment["seg.moe_route"] == 2 * 3
        assert by_segment["seg.mlp"] == 9


@pytest.mark.parametrize("program", sorted(TRAIN))
def test_the_optimizer_lies_under_no_segment(program):
    _lowered, text = _train_step(program)
    # AdamW's denominators are the step's only square roots (the norms
    # take rsqrt): optax names no scope, so the update is what is left.
    roots = [p for p, _ in _paths(text) if p.endswith("/sqrt")]
    assert roots and not any(_segments_on(p) for p in roots)


@pytest.mark.parametrize("program", sorted(TRAIN))
def test_each_train_program_holds_its_segments(program):
    _lowered, text = _train_step(program)
    found = set().union(*(_segments_on(p) for p, _ in _paths(text)))
    assert found == set(SEGMENTS_OF[program])


def test_the_scopes_found_are_the_vocabulary():
    """Every name of the vocabulary is found in the union of the model's
    train programs, and nothing else is."""
    found = set()
    for program in TRAIN:
        _lowered, text = _train_step(program)
        found |= set().union(*(_segments_on(p) for p, _ in _paths(text)))
    assert found == set(profiling.SEGMENTS)
    # and in the source: every scope written is in the vocabulary or is
    # one of the two finer scopes inside a segment
    written = set()
    for path in glob.glob(os.path.join(ROOT, "ray_tpu", "models", "*.py")) \
            + glob.glob(os.path.join(ROOT, "ray_tpu", "ops", "*.py")) \
            + [os.path.join(ROOT, "ray_tpu", "parallel", "moe.py")]:
        with open(path) as f:
            written |= set(re.findall(r'named_scope\("([^"]+)"\)', f.read()))
    assert written == (set(profiling.SEGMENTS) | set(profiling.KERNELS)
                       | {"norm", "rope"})


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_serving_programs_hold_the_segments(program):
    cache = jax.eval_shape(functools.partial(init_kv_cache, CFG, 8, 4))
    tables = _tokens(2, 4)
    if program == "decode_step":
        lowered = jax.jit(functools.partial(decode_step, CFG)).lower(
            _params(), cache, _tokens(2), _tokens(2), tables)
    else:
        lowered = jax.jit(functools.partial(prefill_chunk, CFG)).lower(
            _params(), cache, _tokens(2, 8), _tokens(2), _tokens(2), tables)
    paths = _paths(lowered.compile().as_text())
    found = set().union(*(_segments_on(p) for p, _ in paths))
    assert found == set(SEGMENTS_OF["dense"])
    for path, is_matmul in paths:
        if is_matmul:
            assert len(_segments_on(path)) == 1, path


def test_flash_kernels_carry_their_names():
    """Interpreted here, so the kernels are no custom calls; the names are
    on whatever the calls lower to (tests/test_tpu_aot.py has the v5e's)."""
    q = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)
    grad = jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32, interpret=True).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, q, q).as_text(debug_info=True)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in profiling.KERNELS
        assert re.search(r'"[^"]*\b' + kernel + r'\b[^"]*"', text), kernel
