"""repro.core.vops: the select-based indexing the Pallas kernel relies on
must equal plain indexing bitwise — -0.0, infinities and a NaN in the
selected entry included — and a NaN elsewhere must not leak in."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vops

_F = np.array([[1.5, -0.0, np.inf, 2.0],
               [np.nan, 3.0, -np.inf, 0.0],
               [-2.0, np.nan, 7.0, -0.0]], np.float32)
_I = np.array([[3, -7, 0, 9], [np.iinfo(np.int32).min, 1, 2, -1],
               [5, 5, -5, 0]], np.int32)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("mat", [_F, _I], ids=["f32", "i32"])
def test_row_and_col_reads_and_writes_match_indexing(mat):
    m = jnp.asarray(mat)
    for i in range(mat.shape[0]):
        np.testing.assert_array_equal(_bits(vops.take_row(m, i)),
                                      _bits(mat[i]))
        new = mat[(i + 1) % mat.shape[0]]
        want = mat.copy()
        want[i] = new
        np.testing.assert_array_equal(_bits(vops.put_row(m, i, new)),
                                      _bits(want))
    for j in range(mat.shape[1]):
        np.testing.assert_array_equal(_bits(vops.take_col(m, j)),
                                      _bits(mat[:, j]))
        want = mat.copy()
        want[:, j] = mat[:, 0]
        np.testing.assert_array_equal(
            _bits(vops.put_col(m, j, jnp.asarray(mat[:, 0]))), _bits(want))
        for i in range(mat.shape[0]):
            np.testing.assert_array_equal(
                _bits(vops.take(m[i], j)), _bits(mat[i, j]))


def test_bool_take_iota_and_vsum():
    v = np.array([True, False, False, True])
    for i in range(4):
        assert bool(vops.take(jnp.asarray(v), i)) == v[i]
    np.testing.assert_array_equal(np.asarray(vops.iota(5)), np.arange(5))
    x = np.array([0.5, -1.25, 3.0], np.float32)
    assert float(vops.vsum(jnp.asarray(x))) == float(x.sum())
