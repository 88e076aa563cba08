"""Operation and byte counts against hand sums."""
import pytest

import counts


def test_gemm_counts():
    # C (2 x 3) -= A (2 x 4) B (4 x 3): 2*2*3*4 ops; 4 B * (8 + 12 + 2*6)
    assert counts.gemm(2, 3, 4) == (48.0, 128.0)


def test_lu_updates_small():
    # n = 6, nb = 2: steps at j = 0 (4 rows below) and j = 2 (2 rows)
    assert counts.lu_updates(6, 2) == [counts.gemm(4, 4, 2),
                                       counts.gemm(2, 2, 2)]
    assert sum(o for o, _ in counts.lu_updates(6, 2)) == 2 * 16 * 2 + 2 * 4 * 2


def test_lu_updates_n4096_nb256():
    # sum_j 2 (n - j - nb)^2 nb = 2 * 256 * 256^2 * (1^2 + ... + 15^2)
    ops = sum(o for o, _ in counts.lu_updates(4096, 256))
    assert ops == 2 * 256 * 256**2 * sum(i * i for i in range(1, 16))
    assert ops == pytest.approx(4.16e10, rel=1e-3)


def test_ragged_last_block():
    # n = 5, nb = 2: steps at j = 0 (3 below, w 2), j = 2 (1 below, w 2)
    assert counts.lu_updates(5, 2) == [counts.gemm(3, 3, 2),
                                       counts.gemm(1, 1, 2)]


def test_least_seconds_takes_the_larger_bound_per_update():
    ups = [(2e14, 1.0), (1.0, 3e11)]       # one compute-, one memory-bound
    assert counts.least_seconds(ups, 1e14, 1e11) == pytest.approx(2.0 + 3.0)


def test_roofline_reads_the_peak_of_its_device_kind_only():
    import entries.gemm as entry
    from metrics import update_roofline

    trace = {"devices": 1, "spans": [[0, 2 * 10**9, "bench.window"]],
             "ops": [[0, 10**9, "k.1", "jit_g",
                      "jit(_rgemm_jit)/jit(posit_gemm_f32)/cond/"
                      "branch_0_fun/pallas_call"]]}
    ctx = {"trace": trace, "calls": 1, "cfg": {"n": 4096},
           "entry": entry, "kind": "TPU v5 lite"}
    # 2 * 4096^3 operations at 197 TFLOP/s in one second of kernel time
    assert update_roofline.read(ctx) == pytest.approx(
        100 * 2 * 4096**3 / 1.97e14)
    with pytest.raises(KeyError, match="no peaks"):
        update_roofline.read({**ctx, "kind": "cpu"})
