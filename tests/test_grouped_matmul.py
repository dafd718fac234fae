"""The grouped expert kernels' contract, interpreted on the CPU.

``ops/grouped_matmul.py``'s two Pallas kernels against ``lax.ragged_dot``:
the product and both gradients at group sizes that begin and end anywhere
(an empty group, groups smaller than a row tile, a group over three tiles,
groups that cover a quarter of the rows, all of them), once with the tiles
the shapes give (one column tile, one output tile) and once with so little
VMEM allowed that every grid dimension has several steps. What lies behind
the groups is NaN in ``lhs`` and in the cotangent, and the interpreter
leaves NaN in what a kernel does not write: a row that leaks, or an
operand left unmasked where a group ends, fails the comparison. Then
``rows_multiplied`` against a count of the visits in numpy, and the tiles
the kernels choose at the LFM2 cell's widths and at wider ones.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops.moe_rows import ROW_TILE

M, K, N, G = 1024, 256, 384, 4
SIZES = {
    "nothing_routed_here": [0, 0, 0, 0],
    "an_empty_group": [200, 0, 300, 100],
    "groups_smaller_than_a_tile": [50, 30, 7, 100],
    "a_group_over_three_tiles": [130, 300, 60, 20],
    "whole_tiles": [128, 256, 128, 384],
    "a_quarter_with_garbage_behind": [64, 64, 64, 64],
    "all_of_m": [300, 212, 412, 100],
    "all_of_m_in_one_group": [1024, 0, 0, 0],
}


def _normal(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(jnp.bfloat16)


def _close(got, want, rows=None):
    got = np.asarray(got.astype(jnp.float32))[:rows]
    want = np.asarray(want.astype(jnp.float32))[:rows]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2 * (
        np.abs(want).max() if want.size else 0))


@pytest.mark.parametrize("vmem", ["the_tiles_the_shapes_give", "many_tiles"])
@pytest.mark.parametrize("case", list(SIZES))
def test_product_and_gradients_are_ragged_dot_on_the_groups_rows(
        case, vmem, monkeypatch):
    if vmem == "many_tiles":
        monkeypatch.setattr(gm, "_VMEM_BLOCKS", 400 * 1024)
        assert gm._gmm_columns(K, N) == 128
        assert gm._tgmm_tile(K, N) == (128, 128)
    else:
        assert gm._gmm_columns(K, N) == N and gm._tgmm_tile(K, N) == (K, N)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    routed = sum(SIZES[case])
    live = (jnp.arange(M) < routed)[:, None]
    lhs, rhs, grad = _normal(1, (M, K)), _normal(2, (G, K, N)), _normal(
        3, (M, N))
    got, vjp = jax.vjp(lambda a, b: gm._grouped(a, b, sizes),
                       jnp.where(live, lhs, jnp.nan), rhs)
    got_lhs, got_rhs = vjp(jnp.where(live, grad, jnp.nan))
    want, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes),
                        jnp.where(live, lhs, 0), rhs)
    want_lhs, want_rhs = vjp(jnp.where(live, grad, 0))
    assert got.shape == (M, N) and got.dtype == jnp.bfloat16
    _close(got, want, routed)
    _close(got_lhs, want_lhs, routed)
    _close(got_rhs, want_rhs)        # an empty group's gradient: zeros


@pytest.mark.parametrize("case", list(SIZES))
def test_rows_multiplied_counts_the_visits(case):
    ends = np.cumsum(SIZES[case])
    starts = ends - SIZES[case]
    visits = sum(-(-end // gm.TM) - start // gm.TM
                 for start, end in zip(starts, ends) if end > start)
    assert int(gm.rows_multiplied(SIZES[case], M)) == visits * gm.TM
    assert int(gm.rows_multiplied(np.asarray(SIZES[case]), 4 * M)) \
        == visits * gm.TM          # what lies behind the groups costs nothing
    # never fewer than routed, never a visit more a group than its rows fill
    routed, groups = sum(SIZES[case]), sum(n > 0 for n in SIZES[case])
    assert routed <= visits * gm.TM < routed + 2 * groups * gm.TM or not routed


def test_eight_even_groups_that_straddle_multiply_a_fifth_more_rows():
    """The LFM2 cell's layer: 8 groups of about 512 rows of 32,768, at
    offsets that fall anywhere."""
    sizes = [485, 427, 615, 492, 382, 553, 743, 601]
    got = int(gm.rows_multiplied(sizes, 32768)) / sum(sizes)
    assert 1.0 < got < 1.3


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048), (8192, 8192),
                                 (128, 128), (4096, 14336)])
def test_the_tiles_divide_the_shapes_and_fit_the_vmem_allowed(k, n):
    assert ROW_TILE % gm.TM == 0 and gm.TM % 128 == 0
    assert gm.kernel_accepts(32768, k, n)
    assert not gm.kernel_accepts(32768 + gm.TM, k, n)     # not a row tile
    assert not gm.kernel_accepts(32768, k + 64, n)
    tn = gm._gmm_columns(k, n)
    assert n % tn == 0 and tn % 128 == 0
    assert 4 * (gm.TM * k + k * tn + gm.TM * tn) + 4 * gm.TM * tn \
        <= gm._VMEM_BLOCKS < gm._VMEM_LIMIT or tn == 128
    tk, tn = gm._tgmm_tile(k, n)
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    assert 4 * (gm.TM * (tk + tn) + tk * tn) + 4 * tk * tn <= gm._VMEM_BLOCKS
    if (k, n) in ((2048, 1536), (1536, 2048)):    # the LFM2 cell's: whole
        assert gm._gmm_columns(k, n) == n and (tk, tn) == (k, n)
