"""The grouped matmul of the expert layer: the Pallas kernels (interpret
mode here) against the jnp twin, ``lax.ragged_dot``, forward and both
gradients, with empty groups, groups that are not tile multiples and rows
that belong to no group."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import grouped_matmul as gm

CASES = [
    # (rows, k, n, group sizes)
    (300, 256, 384, [50, 0, 120, 7]),       # an empty group, ragged sizes
    (1000, 256, 256, [300, 300, 0, 400]),   # several row tiles, all rows used
    (257, 128, 256, [0, 200]),              # a leading empty group
    (64, 128, 128, [0, 0, 0]),              # no rows in any group
    (130, 128, 128, [129]),                 # one group over a tile edge
]


def _twin(lhs, rhs, sizes):
    with dispatch.forced("jnp"):
        return dispatch.grouped_matmul(lhs, rhs, sizes)


def _kernel(lhs, rhs, sizes):
    with dispatch.forced("pallas"):
        return dispatch.grouped_matmul(lhs, rhs, sizes)


@pytest.mark.parametrize("m,k,n,sizes", CASES)
def test_grouped_matmul_forward_and_grads_match_twin(m, k, n, sizes):
    rng = np.random.default_rng(m)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    s = jnp.asarray(sizes, jnp.int32)
    want, got = _twin(lhs, rhs, s), _kernel(lhs, rhs, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # rows of no group read zeros
    assert not np.asarray(got)[sum(sizes):].any()
    cot = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    gw = jax.grad(lambda a, b: jnp.sum(_twin(a, b, s) * cot), (0, 1))(
        lhs, rhs)
    gk = jax.grad(lambda a, b: jnp.sum(_kernel(a, b, s) * cot), (0, 1))(
        lhs, rhs)
    for a, b in zip(gk, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-3)
    # an empty group's weight gradient is zeros
    for i, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(gk[1][i]).any()


def test_group_metadata_visits_each_row_tile_of_each_group():
    sizes = jnp.asarray([3, 0, 300, 5], jnp.int32)
    (offsets, group_ids, tiles), n = gm.group_metadata(sizes, 512, 128,
                                                       visit_empty=False)
    steps = list(zip(np.asarray(group_ids)[:int(n)],
                     np.asarray(tiles)[:int(n)]))
    # group 0 in tile 0; group 2 from row 3 to 302: tiles 0, 1, 2;
    # group 3 rows 303-307: tile 2
    assert steps == [(0, 0), (2, 0), (2, 1), (2, 2), (3, 2)]
    assert list(np.asarray(offsets)) == [0, 3, 3, 303, 308]
