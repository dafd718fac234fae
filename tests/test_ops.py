"""Pallas kernel tests (interpret mode on CPU) + collective API tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, rms_norm_fused, softmax_cross_entropy
from ray_tpu.parallel.ring_attention import reference_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_dense(causal):
    B, H, S, D = 2, 2, 64, 16
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    assert jnp.allclose(out, ref, atol=1e-4)


def test_flash_attention_fallback_odd_shapes():
    # D not divisible by 8 -> jax fallback path, still correct.
    B, H, S, D = 1, 2, 12, 5
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-4)


def test_rms_norm_fused_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32,))
    out = rms_norm_fused(x, w, interpret=True)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    ref = (x32 * jax.lax.rsqrt(var + 1e-6)) * w
    assert jnp.allclose(out, ref, atol=1e-5)


def test_softmax_cross_entropy_matches_logsoftmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
    targets = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 32)
    got = softmax_cross_entropy(logits, targets)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1))
    assert jnp.allclose(got, want, atol=1e-5)


def test_collective_group_allreduce_between_actors(ray_start_regular):
    import ray_tpu
    from ray_tpu import collective as col

    @ray_tpu.remote
    class Worker:
        def __init__(self, rank):
            self.rank = rank

        def collective_join(self, world_size, rank, backend, group):
            col.init_collective_group(world_size, rank, backend, group)
            return rank

        def reduce(self, group):
            out = col.allreduce(np.full((4,), float(self.rank + 1)),
                                group_name=group)
            return out

        def gather(self, group):
            return col.allgather(np.asarray([self.rank]), group_name=group)

    workers = [Worker.remote(i) for i in range(3)]
    col.create_collective_group(
        workers, world_size=3, ranks=[0, 1, 2], group_name="g1")
    outs = ray_tpu.get([w.reduce.remote("g1") for w in workers])
    for o in outs:
        np.testing.assert_allclose(o, np.full((4,), 6.0))
    gathered = ray_tpu.get([w.gather.remote("g1") for w in workers])
    for g in gathered:
        assert [int(x[0]) for x in g] == [0, 1, 2]
    col.destroy_collective_group("g1")


def test_in_program_collective_ops(eight_device_mesh):
    from jax.sharding import PartitionSpec as P

    from ray_tpu.collective import ops
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(dp=8)
    x = jnp.arange(8.0)

    f = jax.jit(jax.shard_map(
        lambda x: ops.allreduce(x, "dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, 28.0))

    g = jax.jit(jax.shard_map(
        lambda x: ops.broadcast(x, "dp", root=3),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    np.testing.assert_allclose(np.asarray(g(x)), np.full(8, 3.0))


def test_flash_attention_grads_match_dense():
    """The custom-vjp backward (blockwise recompute) must match dense
    attention gradients (interpret mode on CPU)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import _fallback, flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (2, 2, 256, 16)  # tileable: S % 128 == 0 path would need 128
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_fallback(q, k, v, True, 16 ** -0.5) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


# (causal, sq, sk, block_q, block_k, head_dim, dtype): each case counts.
# Between them: causal and not; block_q ==, > and < block_k; Sq == and !=
# Sk; f32 and bf16; one tile (only a diagonal tile) and a grid of four a
# side; a k-block of two lane-widths (two probability sub-tiles), and a
# head wider than the forward's running statistics by a whole number of
# copies (16 over 8) and not (24 over 16).
_FLASH_CASES = [
    (True, 32, 32, 32, 32, 16, jnp.float32),      # one tile, the diagonal's
    (True, 128, 128, 32, 32, 16, jnp.float32),    # 4 x 4 tiles
    (False, 128, 128, 32, 32, 16, jnp.float32),
    (True, 128, 128, 64, 32, 16, jnp.float32),    # block_q > block_k
    (True, 128, 128, 32, 64, 16, jnp.float32),    # block_q < block_k
    (True, 64, 128, 32, 32, 16, jnp.float32),     # Sq < Sk
    (True, 128, 64, 32, 16, 16, jnp.float32),     # Sq > Sk
    (False, 64, 128, 32, 64, 16, jnp.float32),
    (True, 256, 256, 128, 256, 8, jnp.float32),   # two sub-tiles a k-block
    (True, 64, 64, 32, 8, 16, jnp.float32),       # head_dim 2 x the stats
    (True, 64, 64, 16, 16, 24, jnp.float32),      # head_dim 1.5 x the stats
    (True, 128, 128, 32, 32, 16, jnp.bfloat16),
    (True, 128, 64, 64, 32, 16, jnp.bfloat16),
    (False, 64, 128, 32, 64, 16, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "causal,sq,sk,block_q,block_k,d,dtype", _FLASH_CASES,
    ids=[f"{'causal' if c else 'full'}-{sq}x{sk}-{bq}x{bk}-d{d}-{t.__name__}"
         for c, sq, sk, bq, bk, d, t in _FLASH_CASES])
def test_flash_forward_and_gradients_match_dense(causal, sq, sk, block_q,
                                                 block_k, d, dtype):
    """Forward, dq, dk and dv of the interpreted kernels against
    ``_fallback`` on the same inputs, in units of the reference's
    largest value: rounding of f32, and of bf16's probability tiles."""
    from ray_tpu.ops.flash_attention import _fallback, flash_attention

    keys = jax.random.split(jax.random.PRNGKey(sq + sk + block_q), 3)
    q = jax.random.normal(keys[0], (1, 2, sq, d), dtype)
    k = jax.random.normal(keys[1], (1, 2, sk, d), dtype)
    v = jax.random.normal(keys[2], (1, 2, sk, d), dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=True)

    def dense(q, k, v):
        return _fallback(q, k, v, causal, d ** -0.5)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    def with_grads(fn):
        return (fn(q, k, v),) + jax.grad(loss(fn), argnums=(0, 1, 2))(q, k, v)

    got, want = with_grads(flash), with_grads(dense)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name


_TILE_SHAPES = [(sq, sk, bq, bk)
                for sq, sk in ((32, 32), (64, 64), (96, 96), (64, 128),
                               (128, 64), (96, 32))
                for bq in (8, 16, 32) for bk in (8, 16, 32)]


def test_flash_tile_counts_at_the_cell_and_sum_to_the_grid():
    from ray_tpu.ops.flash_attention import tile_counts

    assert tile_counts(4096, 4096, 512, 512, True) == (28, 8, 28)
    assert tile_counts(4096, 4096, 512, 512, False) == (64, 0, 0)
    assert tile_counts(512, 512, 512, 512, True) == (0, 1, 0)
    for sq, sk, bq, bk in _TILE_SHAPES + [(12288, 12288, 512, 512),
                                          (1024, 4096, 256, 512)]:
        for causal in (True, False):
            counts = tile_counts(sq, sk, bq, bk, causal)
            assert sum(counts) == (sq // bq) * (sk // bk), (sq, sk, bq, bk)
            assert min(counts) >= 0


@pytest.mark.parametrize("sq,sk,bq,bk", _TILE_SHAPES[::3])
def test_flash_tile_split_drops_no_position_and_masks_every_one(sq, sk,
                                                                bq, bk):
    """Brute force over small grids, by rows (forward, dq) and by columns
    (dk/dv): a skipped tile holds no unmasked position, an interior tile
    no masked one, and the two readings agree with ``tile_counts``."""
    from ray_tpu.ops.flash_attention import _col_tiles, _row_tiles, tile_counts

    allowed = np.arange(sq)[:, None] >= np.arange(sk)[None, :]
    num_qb, num_kb = sq // bq, sk // bk

    def tile(qi, ki):
        return allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]

    kinds = {}
    for qi in range(num_qb):
        interior, run = (int(n) for n in _row_tiles(qi, bq, bk, num_kb, True))
        assert 0 <= interior <= run <= num_kb
        for ki in range(num_kb):
            kinds[qi, ki] = ("interior" if ki < interior else
                             "diagonal" if ki < run else "skipped")
    for ki in range(num_kb):
        start, interior = (int(n) for n in
                           _col_tiles(ki, bq, bk, num_qb, True))
        assert 0 <= start <= interior <= num_qb
        for qi in range(num_qb):
            assert kinds[qi, ki] == ("skipped" if qi < start else
                                     "diagonal" if qi < interior else
                                     "interior"), (qi, ki)
    for (qi, ki), kind in kinds.items():
        if kind == "skipped":
            assert not tile(qi, ki).any(), (qi, ki)
        elif kind == "interior":
            assert tile(qi, ki).all(), (qi, ki)
        else:       # the mask is needed: both kinds of position are there
            assert tile(qi, ki).any() and not tile(qi, ki).all(), (qi, ki)
    got = tuple(sum(k == name for k in kinds.values())
                for name in ("interior", "diagonal", "skipped"))
    assert got == tile_counts(sq, sk, bq, bk, True)
