"""The reference's posit codec and error arithmetic."""
import numpy as np
import pytest

import reference as ref

FORMATS = [(32, 2), (16, 1), (8, 2)]


@pytest.mark.parametrize("nbits,es", [(16, 1), (8, 2)])
def test_every_word_round_trips(nbits, es):
    words = np.arange(-(1 << (nbits - 1)), 1 << (nbits - 1)).astype(np.int32)
    values = ref.decode(words, nbits, es)
    nar = words == -(1 << (nbits - 1))
    assert np.isnan(values[nar]).all() and not np.isnan(values[~nar]).any()
    assert np.array_equal(ref.encode(values, nbits, es), words)
    assert np.all(np.diff(values[~nar][1:]) > 0)      # monotone in the word


@pytest.mark.parametrize("nbits,es", FORMATS)
def test_codec_agrees_with_the_program(nbits, es):
    import jax.numpy as jnp
    from repro.core import posit
    from repro.core.formats import PositFormat

    fmt = PositFormat(nbits, es)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(20000) * 10.0 ** rng.integers(-45, 45, 20000)
    x = np.concatenate([x, [0.0, 1.0, -1.0, 1e300, -1e-300, 3.0]])
    words = ref.encode(x, nbits, es)
    assert np.array_equal(words, np.asarray(posit.from_float64(
        jnp.asarray(x), fmt)))
    assert np.array_equal(ref.decode(words, nbits, es), np.asarray(
        posit.to_float64(jnp.asarray(words), fmt)))


def test_rounding_is_to_nearest_even_and_saturates():
    # p8e0 spacing is 1/32 just above 1: 1 + 1/64 ties to 1 (even word)
    assert ref.encode([1.0 + 1 / 64], 8, 0)[0] == ref.encode([1.0], 8, 0)[0]
    assert ref.encode([1.0 + 3 / 64], 8, 0)[0] == 0x42
    maxpos, minpos = ref.decode([0x7F, 1], 8, 2)
    assert maxpos == 2.0 ** 24 and minpos == 2.0 ** -24
    assert list(ref.encode([1e30, 1e-30, -1e30, np.nan], 8, 2)) == [
        0x7F, 1, -0x7F, -0x80]


def test_backward_errors_vanish_on_exact_factors():
    a = np.array([[1.0, 2.0], [3.0, 4.0]])
    # P A = L U with rows swapped: [[3, 4], [1, 2]] = [[1, 0], [1/3, 1]] U
    lu = np.array([[3.0, 4.0], [1 / 3, 2.0 - 4.0 / 3]])
    assert ref.lu_backward_error(a, lu, np.array([1, 1])) < 1e-16
    x = np.linalg.solve(a, [1.0, 1.0])
    assert ref.solve_backward_error(a, x, np.array([1.0, 1.0])) < 1e-15
    assert ref.gemm_error(a @ a, a, a, np.zeros((2, 2)), 1.0, 0.0) == 0.0
    assert ref.gemm_error(np.full((2, 2), np.nan), a, a, a, -1.0,
                          1.0) == np.inf


def test_data_is_the_same_for_the_same_seed():
    a = ref.make_matrix("general", 8, 1.0, 2**31 + 9, 0)
    assert np.array_equal(a, ref.make_matrix("general", 8, 1.0,
                                             2**31 + 9, 0))
    assert not np.array_equal(a, ref.make_matrix("general", 8, 1.0,
                                                 2**31 + 9, 1))
