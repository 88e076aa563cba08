"""Distributed == single-device bit-identity (repro.dist acceptance).

The distributed routines' whole contract is that sharding is a pure
*schedule* change: every posit word out of ``pdgemm`` / ``p_rpotrf`` /
``p_rgetrf`` / ``p_rgesv_ir`` must equal the single-device
``rgemm`` / ``rpotrf`` / ``rgetrf`` / ``rgesv_ir`` word bit-for-bit, for
every gemm backend, on both a 2D (2x2) and a degenerate (1x8 / 8x1)
grid, including non-divisible shapes that exercise padding blocks.
Multi-device cases run through the ``multi_device`` subprocess fixture
(8 forced host devices); the layout index math is pure and tests
in-process.
"""
import numpy as np
import pytest

pytestmark = []

_PRELUDE = """
import os
import numpy as np, jax, jax.numpy as jnp
from repro.core import posit as P
from repro.kernels.ops import rgemm
from repro.lapack import decomp, refine
from repro.dist import (distribute, make_grid_mesh, pdgemm,
                        p_residual_quire, p_rpotrf, p_rgetrf, p_rgesv_ir,
                        p_rposv_ir)

rng = np.random.default_rng(7)
def pm(shape, lo=-6, hi=6):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(lo, hi, shape))
    return P.from_float64(jnp.asarray(x))

def eq(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))
"""


# --------------------------------------------------------------------------
# layout index math (in-process; no devices needed)
# --------------------------------------------------------------------------

def test_block_cyclic_roundtrip_and_owner_math():
    import jax.numpy as jnp
    from repro.dist.layout import BlockCyclic, gather_array, scatter_array

    rng = np.random.default_rng(0)
    for (m, n, nb, p, q) in [(96, 96, 32, 2, 2), (96, 80, 32, 2, 4),
                             (65, 130, 32, 1, 8), (40, 40, 16, 8, 1),
                             (33, 67, 32, 3, 2)]:
        lay = BlockCyclic(m=m, n=n, nb=nb, p=p, q=q)
        x = rng.integers(-2**31, 2**31, (m, n), dtype=np.int32)
        d = scatter_array(jnp.asarray(x), lay)
        assert d.shape == (p * lay.lm, q * lay.ln)
        assert np.array_equal(np.asarray(gather_array(d, lay)), x)
        # scatter places global block (bi, bj) at its cyclic owner
        d_np = np.asarray(d)
        for bi, bj in [(0, 0), (1, 1), (m // nb, n // nb)]:
            if bi * nb >= m or bj * nb >= n:
                continue
            r, c = lay.block_owner(bi, bj)
            t, s = bi // p, bj // q
            tile = d_np[r * lay.lm + t * nb, c * lay.ln + s * nb]
            assert tile == x[bi * nb, bj * nb]


def test_layout_col_block_home():
    from repro.dist.layout import BlockCyclic
    lay = BlockCyclic(m=96, n=96, nb=32, p=2, q=2)
    assert lay.col_block_home(0) == (0, 0, 0)
    assert lay.col_block_home(32) == (1, 0, 0)
    assert lay.col_block_home(64) == (0, 1, 32)


# --------------------------------------------------------------------------
# pdgemm: every backend, 2x2 grid, odd shapes
# --------------------------------------------------------------------------

@pytest.mark.multi_device
def test_pdgemm_bit_identity_all_backends_2x2(multi_device):
    out = multi_device(_PRELUDE + """
mesh = make_grid_mesh(2, 2)
shapes = {"xla_quire": [(96, 96, 96), (96, 80, 64)],
          "quire_exact": [(96, 96, 96), (96, 80, 64)],
          "pallas_split3": [(96, 80, 64)],
          "faithful": [(96, 80, 64)]}
for backend, cases in shapes.items():
    for (m, k, n) in cases:
        a, b = pm((m, k)), pm((k, n))
        got = pdgemm(distribute(a, mesh, 32), distribute(b, mesh, 32),
                     backend=backend).gather()
        assert eq(got, rgemm(a, b, backend=backend)), (backend, m, k, n)
        print("OK", backend, (m, k, n))
print("DONE")
""")
    assert "DONE" in out


@pytest.mark.multi_device
def test_pdgemm_format_knob_p16e1(multi_device):
    """The format-parametric dist contract: pdgemm with fmt=p16e1 (both
    schedules) is bit-identical to single-device rgemm in p16e1 — the
    k_split limb planes shrink to the p16e1 quire's 4 limbs and still
    reassociate exactly."""
    out = multi_device(_PRELUDE + """
from repro.core.formats import P16E1
mesh = make_grid_mesh(2, 2)
x = rng.standard_normal((96, 80)); y = rng.standard_normal((80, 64))
a = P.from_float64(jnp.asarray(x), P16E1)
b = P.from_float64(jnp.asarray(y), P16E1)
ad, bd = distribute(a, mesh, 32), distribute(b, mesh, 32)
for backend, ks in (("xla_quire", False), ("quire_exact", False),
                    ("quire_exact", True)):
    got = pdgemm(ad, bd, backend=backend, k_split=ks, fmt=P16E1).gather()
    assert eq(got, rgemm(a, b, backend=backend, fmt=P16E1)), (backend, ks)
    print("OK", backend, ks)
print("DONE")
""")
    assert "DONE" in out


@pytest.mark.multi_device
def test_limb_psum_scatter_is_exact(multi_device):
    """The k_split reduce-scatter sends int64 limbs as int32 pieces (a
    TPU cannot reduce-scatter int64): the sums of limbs at the headroom
    edge come back exact, and NaR flags OR."""
    out = multi_device(_PRELUDE + """
from jax.sharding import PartitionSpec as S
from repro.launch.collectives import limb_psum_scatter
mesh = make_grid_mesh(1, 8)
r2 = np.random.default_rng(3)
x = r2.integers(-2**59, 2**59, (8 * 4, 64, 3), dtype=np.int64)
x[:, :, 0] = 2**59 - 1
x[::3, :, 1] = -2**59
nar = r2.random((8 * 4, 64)) < 0.01
f = jax.jit(jax.shard_map(
    lambda l, n: limb_psum_scatter(l, n, "col", 1), mesh=mesh,
    in_specs=(S("col"), S("col")), out_specs=(S("col"), S("col"))))
got, got_nar = (np.asarray(v) for v in f(x, nar))
want = x.reshape(8, 4, 8, 8, 3).sum(axis=0).transpose(1, 0, 2, 3).reshape(
    32, 8, 3)
want_nar = nar.reshape(8, 4, 8, 8).any(axis=0).transpose(1, 0, 2).reshape(
    32, 8)
assert np.array_equal(got, want) and np.array_equal(got_nar, want_nar)
print("DONE")
""")
    assert "DONE" in out


@pytest.mark.multi_device
def test_pdgemm_limb_psum_k_split(multi_device):
    """The quire limb-plane reduction schedule: deposits on each device's
    K slab, psum_scatter over the limb planes, ONE rounding — plus the
    alpha/beta folding of the quire_exact contract."""
    out = multi_device(_PRELUDE + """
mesh = make_grid_mesh(2, 2)
a, b, c0 = pm((96, 80)), pm((80, 64)), pm((96, 64))
ad, bd = distribute(a, mesh, 32), distribute(b, mesh, 32)
got = pdgemm(ad, bd, backend="quire_exact", k_split=True).gather()
assert eq(got, rgemm(a, b, backend="quire_exact"))
got = pdgemm(ad, bd, distribute(c0, mesh, 32), alpha=-1.0, beta=1.0,
             backend="quire_exact", k_split=True).gather()
assert eq(got, rgemm(a, b, c0, alpha=-1.0, beta=1.0,
                     backend="quire_exact"))
print("DONE")
""")
    assert "DONE" in out


# --------------------------------------------------------------------------
# distributed factorizations, 2x2 and degenerate grids
# --------------------------------------------------------------------------

@pytest.mark.multi_device
def test_pdecomp_bit_identity_2x2(multi_device):
    out = multi_device(_PRELUDE + """
mesh = make_grid_mesh(2, 2)
n, nb = 96, 32
g = rng.standard_normal((n, n))
sp = P.from_float64(jnp.asarray(g.T @ g + n * np.eye(n)))
gp = P.from_float64(jnp.asarray(g))
for backend in ("xla_quire", "quire_exact", "pallas_split3"):
    got = p_rpotrf(distribute(sp, mesh, nb), gemm_backend=backend).gather()
    assert eq(got, decomp.rpotrf(sp, nb=nb, gemm_backend=backend)), backend
    print("OK rpotrf", backend)
for backend in ("xla_quire", "quire_exact"):
    lu_d, piv_d = p_rgetrf(distribute(gp, mesh, nb), gemm_backend=backend)
    lu, piv = decomp.rgetrf(gp, nb=nb, gemm_backend=backend)
    assert eq(lu_d.gather(), lu) and eq(piv_d, piv), backend
    print("OK rgetrf", backend)
print("DONE")
""", timeout=900)
    assert "DONE" in out


@pytest.mark.multi_device
def test_pdecomp_degenerate_grids(multi_device):
    """1x8 (all-column) and 8x1 (all-row) grids: more devices than real
    blocks on one axis, so some devices hold only padding."""
    out = multi_device(_PRELUDE + """
n, nb = 96, 32
g = rng.standard_normal((n, n))
sp = P.from_float64(jnp.asarray(g.T @ g + n * np.eye(n)))
gp = P.from_float64(jnp.asarray(g))
m18 = make_grid_mesh(1, 8)
m81 = make_grid_mesh(8, 1)
a, b = pm((96, 80)), pm((80, 64))
for mesh, tag in ((m18, "1x8"), (m81, "8x1")):
    ad, bd = distribute(a, mesh, nb), distribute(b, mesh, nb)
    for backend in ("xla_quire", "quire_exact", "pallas_split3",
                    "faithful"):
        got = pdgemm(ad, bd, backend=backend).gather()
        assert eq(got, rgemm(a, b, backend=backend)), (tag, backend)
    got = pdgemm(ad, bd, backend="quire_exact", k_split=True).gather()
    assert eq(got, rgemm(a, b, backend="quire_exact")), (tag, "k_split")
    print("OK pdgemm all backends", tag)
lu_d, piv_d = p_rgetrf(distribute(gp, m18, nb))
lu, piv = decomp.rgetrf(gp, nb=nb)
assert eq(lu_d.gather(), lu) and eq(piv_d, piv)
print("OK rgetrf 1x8")
got = p_rpotrf(distribute(sp, m18, nb), gemm_backend="quire_exact").gather()
assert eq(got, decomp.rpotrf(sp, nb=nb, gemm_backend="quire_exact"))
print("OK rpotrf 1x8 quire_exact")
got = p_rpotrf(distribute(sp, m81, nb)).gather()
assert eq(got, decomp.rpotrf(sp, nb=nb))
print("OK rpotrf 8x1")
lu_d, piv_d = p_rgetrf(distribute(gp, m81, nb))
assert eq(lu_d.gather(), lu) and eq(piv_d, piv)
print("OK rgetrf 8x1")
print("DONE")
""", timeout=900)
    assert "DONE" in out


# --------------------------------------------------------------------------
# distributed iterative refinement
# --------------------------------------------------------------------------

@pytest.mark.multi_device
def test_p_rgesv_ir_matches_single_device(multi_device):
    """Distributed residuals (limb psum) + distributed LU must reproduce
    the single-device refined pair word-for-word — hence the exact same
    digits-gained on the backward-error protocol."""
    out = multi_device(_PRELUDE + """
mesh = make_grid_mesh(2, 2)
n, nb, nrhs = 96, 32, 2
g = rng.standard_normal((n, n))
x64 = rng.standard_normal((n, nrhs))
gp = P.from_float64(jnp.asarray(g))
bp = P.from_float64(jnp.asarray(g @ x64))
ad = distribute(gp, mesh, nb)

# the residual primitive itself
xp = P.from_float64(jnp.asarray(x64[:, 0]))
assert eq(p_residual_quire(ad, xp, bp[:, 0]),
          refine.residual_quire(gp, xp, bp[:, 0]))
print("OK residual")

(hi_d, lo_d), (lu_d, piv_d) = p_rgesv_ir(ad, bp, iters=2)
(hi_s, lo_s), (lu_s, piv_s) = refine.rgesv_ir(gp, bp, iters=2, nb=nb)
assert eq(hi_d, hi_s) and eq(lo_d, lo_s)
assert eq(lu_d.gather(), lu_s) and eq(piv_d, piv_s)
print("OK pair words")

# identical words => identical digits gained over the plain solve
# (column 0; the quire substitution sweeps take vector RHS)
a64 = np.asarray(P.to_float64(gp)); b64 = np.asarray(P.to_float64(bp[:, 0]))
from repro.lapack import solve as S
x_plain = np.asarray(P.to_float64(S.rgetrs(lu_s, piv_s, bp[:, 0],
                                           quire=True)))
x_ir = np.asarray(refine.pair_to_float64(hi_d[:, 0], lo_d[:, 0]))
def berr(x):
    r = b64 - a64 @ x
    return np.linalg.norm(r) / (np.linalg.norm(a64) * np.linalg.norm(x)
                                + np.linalg.norm(b64))
digits = np.log10(berr(x_plain) / berr(x_ir))
assert digits >= 2.0, digits
print("digits_gained %.2f" % digits)
print("DONE")
""", timeout=900)
    assert "DONE" in out


@pytest.mark.multi_device
def test_p_rposv_ir_matches_single_device(multi_device):
    out = multi_device(_PRELUDE + """
mesh = make_grid_mesh(2, 2)
n, nb = 96, 32
g = rng.standard_normal((n, n))
sp64 = g.T @ g + n * np.eye(n)
x64 = rng.standard_normal(n)
sp = P.from_float64(jnp.asarray(sp64))
bp = P.from_float64(jnp.asarray(sp64 @ x64))
(hi_d, lo_d), l_d = p_rposv_ir(distribute(sp, mesh, nb), bp, iters=2)
(hi_s, lo_s), l_s = refine.rposv_ir(sp, bp, iters=2, nb=nb)
assert eq(hi_d, hi_s) and eq(lo_d, lo_s) and eq(l_d.gather(), l_s)
print("DONE")
""", timeout=900)
    assert "DONE" in out
