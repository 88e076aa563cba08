"""positscope (repro.obs) acceptance tests.

The contract under test, in order of importance:

1. **Zero-cost when disabled** — with no collector open (or with tracer
   inputs, i.e. the caller is being traced into an outer jit), the
   instrumented entry points dispatch the ORIGINAL jitted programs:
   lowered text is byte-identical and results are bit-identical.
2. **Bit-identical when enabled** — the collect-variant programs return
   the same words as the plain ones (telemetry is read-only).
3. **Histograms are right** — regime-width / scale histograms and
   golden-zone occupancy match an independent pure-Python bit-level
   oracle (tests/posit_oracle.py style, exact Fractions) on p32e2 /
   p16e1 / p8e2.
4. Spans nest, serialize to Chrome trace_event JSON, and round-trip.
5. The hlo_analysis dtype table covers the int64 limb planes (the s64
   regression) and the IR sweep series shows a contracting residual.
"""
from __future__ import annotations

import json
import re
from fractions import Fraction

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P8E2, P16E1, P32E2
from repro import obs
from repro.kernels import ops
from repro.lapack import decomp, qr, refine, solve
from repro.launch import hlo_analysis

import posit_oracle


def _pm(rng, shape, fmt=P32E2, lo=-6, hi=6):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(lo, hi, shape))
    return posit.from_float64(jnp.asarray(x), fmt)


# --------------------------------------------------------------------------
# 1. zero-cost when disabled
# --------------------------------------------------------------------------

def test_disabled_lowering_identical():
    """Tracing the public wrapper into an outer jit lowers to the SAME
    text as the underlying jitted program — even with a collector open
    (tracer inputs disable the obs path at the Python level)."""
    a = _pm(np.random.default_rng(0), (32, 32))
    spd = ops.rgemm(a, a, trans_b=True)

    wrapped = jax.jit(lambda x: decomp.rgetrf(x, nb=16)).lower(a).as_text()
    direct = jax.jit(lambda x: decomp._rgetrf_jit(x, nb=16)
                     ).lower(a).as_text()
    assert wrapped == direct

    with obs.scoped():
        wrapped_open = jax.jit(
            lambda x: decomp.rgetrf(x, nb=16)).lower(a).as_text()
    assert wrapped_open == direct

    w2 = jax.jit(lambda x: decomp.rpotrf(x, nb=16)).lower(spd).as_text()
    d2 = jax.jit(lambda x: decomp._rpotrf_jit(x, nb=16)).lower(spd).as_text()
    assert w2 == d2

    w3 = jax.jit(lambda x: ops.rgemm(x, x)).lower(a).as_text()
    d3 = jax.jit(lambda x: ops._rgemm_jit(x, x)).lower(a).as_text()
    assert w3 == d3


# the scopes each lowered program carries in its op metadata (n = 64,
# nb = 16): every layer it runs, and no other
_SCOPED = {
    "rgetrf": {"posit.panel", "posit.swap", "posit.trsm", "posit.update"},
    "rpotrf": {"posit.panel", "posit.trsm", "posit.update"},
    "rgetrs": {"posit.swap", "posit.sweep"},
    "rgetrs_quire": {"posit.swap", "posit.quire_sweep"},
    "rgemm": {"posit.update"},
    "rgesv_ir": {"posit.panel", "posit.swap", "posit.trsm", "posit.update",
                 "posit.quire_sweep", "posit.quire_residual",
                 "posit.pair_update"},
}
_SCOPE = re.compile(r'["/](posit\.[a-z_]+)/')


def _scoped_programs(n=64, nb=16):
    rng = np.random.default_rng(4)
    a = _pm(rng, (n, n))
    spd = ops.rgemm(a, a, trans_b=True)
    b = _pm(rng, (n,))
    ipiv = jnp.arange(n, dtype=jnp.int32)
    return {
        "rgetrf": (lambda x: decomp.rgetrf(x, nb=nb), (a,)),
        "rpotrf": (lambda x: decomp.rpotrf(x, nb=nb), (spd,)),
        "rgetrs": (lambda lu, p, y: solve.rgetrs(lu, p, y), (a, ipiv, b)),
        "rgetrs_quire": (lambda lu, p, y: solve.rgetrs(lu, p, y, quire=True),
                         (a, ipiv, b)),
        "rgemm": (lambda x, y: ops.rgemm(x, y, x, alpha=-1.0, beta=1.0),
                  (a, a)),
        "rgesv_ir": (lambda x, y: refine.rgesv_ir(x, y, iters=2, nb=nb),
                     (a, b)),
    }


@pytest.mark.parametrize("program", sorted(_SCOPED))
def test_lowered_program_carries_its_layer_scopes(program):
    fn, args = _scoped_programs()[program]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert set(_SCOPE.findall(text)) == _SCOPED[program]
    assert _SCOPED[program] <= set(obs.scopes.ALL)


def test_scopes_are_metadata_only():
    """Without debug info the lowered program is the same with the
    scopes left out (jax.named_scope made a no-op)."""
    fn, args = _scoped_programs(n=32)["rgesv_ir"]
    with_scopes = jax.jit(fn).lower(*args).as_text()
    import contextlib
    from unittest import mock
    jax.clear_caches()                  # retrace the inner jitted helpers
    try:
        with mock.patch.object(jax, "named_scope",
                               lambda name: contextlib.nullcontext()):
            bare = jax.jit(fn).lower(*args)
    finally:
        jax.clear_caches()
    assert "posit.swap" not in bare.as_text(debug_info=True)
    assert bare.as_text() == with_scopes


def test_pallas_kernel_is_named_for_a_tpu():
    a = _pm(np.random.default_rng(6), (128, 128))
    text = jax.jit(
        lambda x: ops.rgemm(x, x, x, alpha=-1.0, beta=1.0,
                            backend="pallas_split3")
    ).trace(a).lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "posit_gemm_f32_p32e2"' in text


def test_entry_points_open_host_spans():
    """Each public entry opens its ``posit.<entry>`` span on the plain
    path too: a profiler annotation, entered here while the entry is
    traced (``jax.eval_shape``, nothing compiles)."""
    rng = np.random.default_rng(8)
    a = _pm(rng, (32, 32))
    b = _pm(rng, (32,))
    annotated = []

    class _Annotation:
        def __init__(self, name, **kw):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    spd = ops.rgemm(a, a, trans_b=True)
    calls = {
        "posit.rgetrf": lambda: decomp.rgetrf(a, nb=16),
        "posit.rpotrf": lambda: decomp.rpotrf(spd, nb=16),
        "posit.rgemm": lambda: ops.rgemm(a, a),
        "posit.rgesv_ir": lambda: refine.rgesv_ir(a, b, iters=1, nb=16),
        "posit.rposv_ir": lambda: refine.rposv_ir(spd, b, iters=1, nb=16),
    }
    from unittest import mock
    with mock.patch.object(jax.profiler, "TraceAnnotation", _Annotation):
        for name, call in calls.items():
            annotated.clear()
            jax.eval_shape(call)
            assert annotated[0] == name
            assert "ir.sweep" not in annotated  # no collector: plain path
    assert "posit.rpotrf" in annotated          # rposv_ir's factorization


def test_disabled_recorders_are_noops():
    assert not obs.enabled()
    obs.inc("x")                  # all must be safe with no collector
    obs.gauge("x", 1.0)
    obs.observe("x", 2.0)
    obs.record("x", a=1)
    with obs.span("nope"):
        pass
    # active() needs an open collector even for concrete inputs
    assert obs.active(jnp.zeros(3)) is False


# --------------------------------------------------------------------------
# 2. bit-identical when enabled
# --------------------------------------------------------------------------

def test_enabled_bit_identity():
    rng = np.random.default_rng(1)
    n = 48
    a64 = rng.standard_normal((n, n))
    ap = posit.from_float64(jnp.asarray(a64))
    sp = posit.from_float64(jnp.asarray(a64.T @ a64 + n * np.eye(n)))
    bp = posit.from_float64(jnp.asarray(rng.standard_normal((n, 2))))
    rect = posit.from_float64(jnp.asarray(rng.standard_normal((n, n // 2))))

    lu0 = decomp.rgetrf(ap, nb=16)
    l0 = decomp.rpotrf(sp, nb=16)
    qr0 = qr.rgeqrf(rect, nb=16)
    (hi0, lo0), _ = refine.rgesv_ir(ap, bp, iters=2, nb=16)
    g0 = ops.rgemm(ap, ap)
    with obs.scoped() as m:
        lu1 = decomp.rgetrf(ap, nb=16)
        l1 = decomp.rpotrf(sp, nb=16)
        qr1 = qr.rgeqrf(rect, nb=16)
        (hi1, lo1), _ = refine.rgesv_ir(ap, bp, iters=2, nb=16)
        g1 = ops.rgemm(ap, ap)
    for x, y in zip(jax.tree_util.tree_leaves((lu0, l0, qr0, hi0, lo0, g0)),
                    jax.tree_util.tree_leaves((lu1, l1, qr1, hi1, lo1, g1))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    d = m.to_dict()
    # rgesv_ir factorizes through the observed rgetrf too -> 2 calls
    assert d["counters"]["rgetrf.calls"] == 2
    work = decomp.rank1_work(n, n, 16)
    for part in ("panel", "trsm"):
        assert d["counters"][f"posit.{part}.work"] == 2 * work[part][0]
        assert (d["counters"][f"posit.{part}.work_masked"]
                == 2 * work[part][1])
    assert d["counters"]["rpotrf.calls"] == 1
    assert d["counters"]["rgeqrf.calls"] == 1
    assert len(d["series"]["rgetrf.step"]) == 6      # ceil(48/16) x 2 calls
    assert "rgemm.out.golden_zone" in d["gauges"]


# --------------------------------------------------------------------------
# 3. numerics vs the pure-Python oracle
# --------------------------------------------------------------------------

def _oracle_word_stats(pattern: int, nbits: int, es: int):
    """(is_zero, is_nar, reg_len, scale, golden) from first-principles
    bit parsing + exact Fractions — no shared code with repro.obs."""
    mask = (1 << nbits) - 1
    p = pattern & mask
    if p == 0:
        return True, False, 0, 0, False
    if p == 1 << (nbits - 1):
        return False, True, 0, 0, False
    if p >> (nbits - 1):
        p = (-p) & mask
    bits = [(p >> i) & 1 for i in range(nbits - 2, -1, -1)]
    r0 = bits[0]
    m = 1
    while m < len(bits) and bits[m] == r0:
        m += 1
    k = (m - 1) if r0 == 1 else -m
    reg_len = min(m + 1, nbits - 1)                  # run + terminator
    rest = bits[m + 1:] if m < len(bits) else []
    e = 0
    for b in rest[:es]:
        e = 2 * e + b
    e <<= es - len(rest[:es])
    scale = k * (1 << es) + e
    val = abs(posit_oracle.decode(pattern, nbits, es))
    lo = Fraction(2) ** -(1 << es)
    hi = Fraction(2) ** (1 << es)
    golden = lo <= val < hi
    assert golden == (k in (0, -1))                  # two defs, one zone
    return False, False, reg_len, scale, golden


@pytest.mark.parametrize("fmt", [P32E2, P16E1, P8E2],
                         ids=lambda f: f.name)
def test_collect_numerics_vs_oracle(fmt):
    rng = np.random.default_rng(7)
    if fmt.nbits <= 16:
        # every non-NaR pattern of the format
        half = 1 << (fmt.nbits - 1)
        words = np.arange(-half + 1, half, dtype=np.int64)
        words = rng.permutation(words)[:4096]
    else:
        x = rng.standard_normal(4096) * np.exp2(rng.uniform(-24, 24, 4096))
        words = np.asarray(posit.from_float64(jnp.asarray(x), fmt),
                           np.int64)
    st = obs.collect_numerics(jnp.asarray(words, jnp.int32), fmt)

    reg_hist: dict[int, int] = {}
    scale_hist: dict[int, int] = {}
    nz = nnar = ngold = nfin = 0
    reg_sum = 0
    for w in words:
        z, nar, reg_len, scale, golden = _oracle_word_stats(
            int(w), fmt.nbits, fmt.es)
        if z:
            nz += 1
            continue
        if nar:
            nnar += 1
            continue
        nfin += 1
        reg_sum += reg_len
        ngold += golden
        reg_hist[reg_len] = reg_hist.get(reg_len, 0) + 1
        scale_hist[scale] = scale_hist.get(scale, 0) + 1

    assert int(st["zero"]) == nz
    assert int(st["nar"]) == nnar
    got_reg = {i: int(v) for i, v in enumerate(np.asarray(st["regime_hist"]))
               if v}
    got_scale = {i - fmt.max_scale: int(v)
                 for i, v in enumerate(np.asarray(st["scale_hist"])) if v}
    assert got_reg == reg_hist
    assert got_scale == scale_hist
    assert float(st["golden_frac"]) == pytest.approx(ngold / max(nfin, 1))
    assert float(st["regime_mean"]) == pytest.approx(reg_sum / max(nfin, 1))


def test_golden_zone_bounds():
    assert obs.golden_zone_bounds(P32E2) == (1 / 16, 16.0)
    assert obs.golden_zone_bounds(P16E1) == (1 / 4, 4.0)
    assert obs.golden_zone_bounds(P8E2) == (1 / 16, 16.0)
    # exactly-at-bounds membership: lo is in, hi is out
    w = posit.from_float64(jnp.asarray([1 / 16, 15.9, 16.0, 0.05]), P32E2)
    assert obs.golden_zone_fraction(w, P32E2) == pytest.approx(0.5)


def test_encode_round_stats():
    # exactly-representable values round nowhere; 1/3 always rounds;
    # huge values saturate
    st = obs.encode_round_stats(jnp.asarray([1.0, 1.5, -2.25, 0.0]), P32E2)
    assert int(st["total"]) == 3                     # zero not counted
    assert int(st["rounded"]) == 0
    assert int(st["saturated"]) == 0
    st = obs.encode_round_stats(jnp.asarray([1 / 3, 1e300, 1e-300]), P32E2)
    assert int(st["rounded"]) == 1
    assert int(st["saturated"]) == 2


def test_log2_bucket():
    from repro.obs.metrics import ZERO_BUCKET, log2_bucket
    assert log2_bucket(1.0) == 0
    assert log2_bucket(0.5) == -1
    assert log2_bucket(3.0) == 1
    assert log2_bucket(-4.0) == 2
    assert log2_bucket(0.0) == ZERO_BUCKET
    assert log2_bucket(float("nan")) == ZERO_BUCKET


def test_quire_carry_stats():
    rng = np.random.default_rng(3)
    a = _pm(rng, (8, 64), lo=-2, hi=2)
    b = _pm(rng, (64, 8), lo=-2, hi=2)
    from repro.quire import quire_gemm_limbs
    limbs, _ = quire_gemm_limbs(a, b, P32E2)
    st = obs.quire_carry_stats(limbs)
    per = np.asarray(st["per_limb"])
    assert per.shape == (limbs.shape[-1],)
    assert int(st["total"]) == per.sum()
    assert int(st["total"]) > 0                      # deposits do carry
    assert int(obs.quire_carry_stats(jnp.zeros((4, 16), jnp.int64))
               ["total"]) == 0


# --------------------------------------------------------------------------
# 4. spans + chrome trace
# --------------------------------------------------------------------------

def test_span_nesting_and_chrome_roundtrip(tmp_path):
    with obs.scoped() as m:
        with obs.span("outer", size=3):
            with obs.span("inner"):
                pass
    names = {e["name"]: e for e in m.events}
    assert set(names) == {"outer", "inner"}
    assert names["inner"]["args"]["path"] == "outer.inner"
    assert names["inner"]["args"]["depth"] == 2
    assert names["outer"]["args"]["size"] == 3
    assert names["inner"]["ts"] >= names["outer"]["ts"]
    assert names["inner"]["dur"] <= names["outer"]["dur"]

    path = tmp_path / "trace.json"
    m.save_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        for key in ("ts", "dur", "pid", "tid", "name", "cat", "args"):
            assert key in ev


def test_scoped_nesting_and_json():
    with obs.scoped() as outer:
        obs.inc("n")
        with obs.scoped() as inner:
            obs.inc("n", 2)
        obs.inc("n")
    assert inner.counters["n"] == 2                  # only while open
    assert outer.counters["n"] == 4
    json.loads(outer.to_json())                      # JSON-clean


# --------------------------------------------------------------------------
# 5. IR sweep series + hlo_analysis regression
# --------------------------------------------------------------------------

def test_ir_sweep_series():
    rng = np.random.default_rng(5)
    n = 40
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal(n)
    ap = posit.from_float64(jnp.asarray(a))
    bp = posit.from_float64(jnp.asarray(b))
    with obs.scoped() as m:
        refine.rgesv_ir(ap, bp, iters=3, nb=16)
    rows = m.to_dict()["series"]["ir.sweep"]
    assert [r["sweep"] for r in rows] == [0, 1, 2]
    norms = [r["r_norm"] for r in rows]
    assert norms[-1] < norms[0]                      # refinement contracts
    assert rows[-1]["digits_gained"] > 2
    assert all(isinstance(r["limb_carries"], int) for r in rows)


# Optimized-HLO lines as emitted by jaxlib's CPU SPMD partitioner for the
# k_split pdgemm / limb-psum programs (captured shapes): the limb planes
# are s64 — with s64 missing from the dtype table these counted 0 bytes.
_HLO_SNIPPET = """\
  %all-reduce.1 = s64[4,2,16]{2,1,0} all-reduce(s64[4,2,16]{2,1,0} %x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %all-reduce.2 = s32[4,2]{1,0} all-reduce(s32[4,2]{1,0} %n), replica_groups={{0,1},{2,3}}, to_apply=%add
  %reduce-scatter.1 = s64[32,16,16]{2,1,0} reduce-scatter(s64[32,32,16]{2,1,0} %l), dimensions={1}, to_apply=%add
  %all-gather.1 = c64[8,4]{1,0} all-gather(c64[2,4]{1,0} %g), dimensions={0}
"""


def test_collective_bytes_int64_and_complex():
    got = hlo_analysis.collective_bytes(_HLO_SNIPPET)
    assert got["all-reduce"] == 4 * 2 * 16 * 8 + 4 * 2 * 4
    assert got["reduce-scatter"] == 32 * 16 * 16 * 8
    assert got["all-gather"] == 8 * 4 * 8            # c64 is 8 bytes
    for dt in ("s64", "u64", "c64", "c128"):
        assert dt in hlo_analysis._BYTES


# --------------------------------------------------------------------------
# 6. distributed byte accounting (plan vs HLO vs runtime), 2x2 grid
# --------------------------------------------------------------------------

@pytest.mark.multi_device
def test_pdgemm_collective_accounting(multi_device):
    out = multi_device("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro import obs
        from repro.core import posit
        from repro.core.formats import P32E2
        from repro.dist import layout, pblas
        from repro.launch import hlo_analysis

        n, nb = 64, 16
        mesh = jax.make_mesh((2, 2), ("row", "col"))
        rng = np.random.default_rng(0)
        A = layout.distribute(posit.from_float64(
            jnp.asarray(rng.standard_normal((n, n)))), mesh, nb)
        B = layout.distribute(posit.from_float64(
            jnp.asarray(rng.standard_normal((n, n)))), mesh, nb)
        lay = A.layout
        c0 = jax.device_put(
            jnp.zeros((lay.p * lay.lm, lay.q * lay.ln), jnp.int32),
            jax.sharding.NamedSharding(mesh, pblas._SPEC))
        for k_split, backend in ((False, "xla_quire"),
                                 (True, "quire_exact")):
            plan = pblas.pdgemm_collective_plan(lay, lay, k_split=k_split)
            hlo = hlo_analysis.collective_bytes(pblas._pdgemm_sharded.lower(
                A.data, B.data, c0, lay_a=lay, lay_b=lay, mesh=mesh,
                alpha=1.0, beta=0.0, backend=backend, k_split=k_split,
                fmt=P32E2).compile().as_text())
            with obs.scoped() as m:
                pblas.pdgemm(A, B, backend=backend, k_split=k_split)
            pre = "dist.pdgemm."
            run = {k[len(pre):-len(".bytes")]: int(v)
                   for k, v in m.to_dict()["counters"].items()
                   if k.startswith(pre) and k.endswith(".bytes")}
            assert plan == hlo == run, (k_split, plan, hlo, run)
        # residual accounting: plan vs runtime counters
        x = posit.from_float64(jnp.asarray(rng.standard_normal(n)))
        b = posit.from_float64(jnp.asarray(rng.standard_normal(n)))
        with obs.scoped() as m:
            pblas.p_residual_quire(A, x, b, jnp.zeros_like(x))
        pre = "dist.p_residual."
        run = {k[len(pre):-len(".bytes")]: int(v)
               for k, v in m.to_dict()["counters"].items()
               if k.startswith(pre) and k.endswith(".bytes")}
        assert run == pblas.p_residual_plan(lay, 1)
        print("ACCOUNTING_OK")
    """)
    assert "ACCOUNTING_OK" in out
