"""Parallelism-layer tests on the 8-device virtual CPU mesh.

Mirrors the reference's fake-communicator strategy (SURVEY.md §4): GPU/NCCL
paths there run CPU-only via mocked comm groups; here the ICI-collective
paths run on a virtual 8-device mesh, asserting exact numerical parity with
unsharded references.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (
    MeshConfig,
    make_mesh,
    pipeline_spmd,
    ring_attention,
    ulysses_attention,
)
from ray_tpu.parallel.ring_attention import reference_attention


def test_mesh_config_factoring(eight_device_mesh):
    assert MeshConfig(dp=-1, tp=2).sizes(8) == (4, 1, 1, 2, 1, 1)
    assert MeshConfig(dp=2, pp=2, tp=2).sizes(8) == (2, 1, 2, 2, 1, 1)
    with pytest.raises(ValueError):
        MeshConfig(dp=3).sizes(8)
    mesh = make_mesh(dp=2, tp=4)
    assert dict(zip(mesh.axis_names, mesh.devices.shape))["tp"] == 4


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(eight_device_mesh, causal):
    mesh = make_mesh(sp=8)
    B, H, S, D = 2, 4, 32, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D))
               for i in range(3))
    ref = reference_attention(q, k, v, causal=causal)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                       causal=causal),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False))
    assert jnp.allclose(f(q, k, v), ref, atol=1e-4)


def test_ulysses_matches_dense(eight_device_mesh):
    mesh = make_mesh(sp=8)
    B, H, S, D = 2, 8, 32, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D))
               for i in range(3))
    ref = reference_attention(q, k, v, causal=True)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False))
    assert jnp.allclose(f(q, k, v), ref, atol=1e-4)


def test_pipeline_matches_sequential_and_grads(eight_device_mesh):
    mesh = make_mesh(pp=4)
    M, B, D = 8, 2, 16
    Ws = jax.random.normal(jax.random.PRNGKey(3), (4, D, D)) * 0.3
    xs = jax.random.normal(jax.random.PRNGKey(4), (M, B, D))

    def stage_fn(w, a):
        return jnp.tanh(a @ w)

    f = jax.jit(jax.shard_map(
        lambda Ws, xs: pipeline_spmd(
            lambda w, a: stage_fn(w[0], a), Ws, xs, axis_name="pp"),
        mesh=mesh, in_specs=(P("pp", None, None), P()), out_specs=P(),
        check_vma=False))

    want = xs
    for i in range(4):
        want = jax.vmap(lambda a: stage_fn(Ws[i], a))(want)
    assert jnp.allclose(f(Ws, xs), want, atol=1e-5)

    def loss_pp(Ws):
        return jnp.sum(f(Ws, xs) ** 2)

    def loss_seq(Ws):
        w = xs
        for i in range(4):
            w = jax.vmap(lambda a: stage_fn(Ws[i], a))(w)
        return jnp.sum(w ** 2)

    g1, g2 = jax.grad(loss_pp)(Ws), jax.grad(loss_seq)(Ws)
    assert jnp.allclose(g1, g2, atol=1e-4)


def test_distributed_single_host_bootstrap():
    """jax.distributed-shaped bootstrap degenerates cleanly on one host."""
    from ray_tpu.parallel import distributed as dist

    dist.initialize()  # no coordinator: single-process no-op
    assert dist.is_initialized()
    assert dist.process_count() == 1
    assert dist.process_index() == 0
    start, size = dist.host_local_batch_slice(64)
    assert (start, size) == (0, 64)
    dist.shutdown()
    assert not dist.is_initialized()


def test_hybrid_mesh_axis_tiers(eight_device_mesh):
    """DCN axes outermost, ICI axes inner; ICI-bound axes rejected on DCN."""
    import pytest as _pytest

    from ray_tpu.parallel.distributed import HybridMeshConfig, \
        make_hybrid_mesh

    mesh = make_hybrid_mesh(
        HybridMeshConfig(dcn={"dp": 2}, ici={"tp": 2, "sp": 2}),
        devices=eight_device_mesh)
    assert mesh.axis_names == ("dp", "tp", "sp")
    assert mesh.shape == {"dp": 2, "tp": 2, "sp": 2}
    # An ICI-bound axis on the DCN tier is a layout bug — rejected.
    with _pytest.raises(ValueError, match="must not cross DCN"):
        make_hybrid_mesh(HybridMeshConfig(dcn={"tp": 2}, ici={"dp": 4}),
                         devices=eight_device_mesh)


def test_hybrid_mesh_runs_collectives(eight_device_mesh):
    """A psum over each tier of the hybrid mesh executes correctly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.distributed import HybridMeshConfig, \
        make_hybrid_mesh

    mesh = make_hybrid_mesh(
        HybridMeshConfig(dcn={"dp": 2}, ici={"tp": 4}),
        devices=eight_device_mesh)

    def f(x):
        return jax.lax.psum(jax.lax.psum(x, "tp"), "dp")

    x = jnp.arange(8.0)
    out = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(("dp", "tp")), out_specs=P(("dp", "tp")),
        check_vma=False))(x)
    assert float(out.sum()) == float(x.sum()) * 8
