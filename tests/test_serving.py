"""Posit-quantized serving: weight round-trips against the rational
oracle, quantized forward through every family, scanned prefill pinned
bit-identical to the per-token loop, and the continuous-batching
engine's batched == sequential identity over the paged posit KV-cache.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import posit_oracle as oracle
from repro.configs import get_tiny_config, tiny_config
from repro.core import posit
from repro.core.formats import get_format
from repro.models import forward_prefill, init_params
from repro.models.common import Axes
from repro.serving import (Engine, PagedKVSpec, PagePool, QuantConfig,
                           Request, generate, param_bytes, prefill,
                           prefill_loop, quantize_params,
                           weight_golden_zone)
from repro.serving.quantize import (channel_scale_exp, dequant_leaf,
                                    quant_matmul, quantize_leaf)

FMTS = ("p32e2", "p16e1", "p8e2")


def _leaf(w):
    return {"w": jnp.asarray(w, jnp.float32), "axes": Axes((None,) * w.ndim)}


# --------------------------------------------------------------------------
# round-trips / scales / hygiene
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fmt_name", FMTS)
def test_pack_unpack_matches_oracle(fmt_name):
    """Every packed word equals the rational oracle's nearest-even
    encode of the equilibrated weight, and unpack returns exactly the
    oracle's value of that word (scaled back)."""
    fmt = get_format(fmt_name)
    rng = np.random.default_rng(3)
    w = (rng.standard_normal((12, 5)) *
         np.exp2(rng.integers(-6, 7, (12, 5)))).astype(np.float32)
    ql = quantize_leaf(_leaf(w), QuantConfig(fmt=fmt_name))
    words = np.asarray(ql["qw"], np.int64)
    sexp = np.asarray(ql["sexp"], np.int64)
    deq = np.asarray(dequant_leaf(ql))
    for i in range(w.shape[0]):
        for j in range(w.shape[1]):
            from fractions import Fraction
            scaled = Fraction(float(w[i, j])) / Fraction(2) ** int(sexp[j])
            want = oracle.encode(scaled, fmt.nbits, fmt.es)
            assert int(words[i, j]) == want, (fmt_name, i, j)
            val = oracle.decode(want, fmt.nbits, fmt.es)
            back = val * Fraction(2) ** int(sexp[j])
            assert float(back) == deq[i, j], (fmt_name, i, j)


@pytest.mark.parametrize("fmt_name", FMTS)
def test_lattice_roundtrip_value_exact(fmt_name):
    """Weights already on the (channel-scaled) posit lattice round-trip
    pack -> unpack exactly."""
    fmt = get_format(fmt_name)
    rng = np.random.default_rng(0)
    # lattice points inside one binade [1,2) (regime k=0, uniform
    # fraction spacing — closed under the quantizer's own pow2
    # equilibration) x exact pow2 channel scales
    mag = rng.uniform(1.0, 2.0, (16, 6)) * rng.choice([-1.0, 1.0], (16, 6))
    raw = np.asarray(
        posit.to_float32_bits(
            posit.from_float32_bits(
                jnp.asarray(mag, jnp.float32), fmt), fmt))
    scales = np.exp2(rng.integers(-8, 9, (6,))).astype(np.float32)
    w = raw * scales
    ql = quantize_leaf(_leaf(w), QuantConfig(fmt=fmt_name))
    assert np.array_equal(np.asarray(dequant_leaf(ql)), w)


def test_channel_scales_exactly_invertible():
    """2^e scaling is exact in f32: scale then unscale is the identity
    for every leaf magnitude the initializer produces."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((32, 8)) * 1e-3, jnp.float32)
    e = channel_scale_exp(w).astype(jnp.float32)
    down = w * jnp.exp2(-e)[None, :]
    up = down * jnp.exp2(e)[None, :]
    assert np.array_equal(np.asarray(up), np.asarray(w))
    # and the scale puts each nonzero channel's max into [1, 2)
    mx = np.abs(np.asarray(down)).max(axis=0)
    assert ((mx >= 1.0) & (mx < 2.0)).all()


def test_stacked_leaf_scales_per_layer():
    """A stacked (np_, d_in, d_out) scan leaf gets independent
    per-layer-per-channel scales (reduction over the contraction axis
    only)."""
    rng = np.random.default_rng(2)
    w = np.stack([rng.standard_normal((6, 4)),
                  rng.standard_normal((6, 4)) * 1024.0])
    e = np.asarray(channel_scale_exp(jnp.asarray(w, jnp.float32)))
    assert e.shape == (2, 4)
    assert (e[1] > e[0]).all()


def test_nar_hygiene_and_saturation():
    wn = np.ones((4, 4), np.float32)
    wn[1, 2] = np.nan
    with pytest.raises(ValueError, match="NaR"):
        quantize_params({"lin": {"w": _leaf(wn)}})
    qp = quantize_params({"lin": {"w": _leaf(wn)}}, allow_nar=True)
    fmt = get_format("p16e1")
    nar = np.asarray(posit.is_nar(
        jnp.asarray(qp["lin"]["w"]["qw"], jnp.int32), fmt))
    assert nar.sum() == 1 and nar[1, 2]
    # out-of-range weights saturate at +-maxpos (per_channel=False keeps
    # raw magnitudes) — finite, no NaR
    big = np.full((2, 3), 1e30, np.float32)
    qb = quantize_leaf(_leaf(big),
                       QuantConfig(fmt="p8e2", per_channel=False))
    deq = np.asarray(dequant_leaf(qb))
    assert np.isfinite(deq).all()
    assert not np.asarray(posit.is_nar(
        jnp.asarray(qb["qw"], jnp.int32), get_format("p8e2"))).any()


def test_param_bytes_storage_saving():
    cfg = get_tiny_config("qwen2-0.5b", policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, QuantConfig(fmt="p16e1"))
    pb = param_bytes(qp)
    assert pb["q_f32_bytes"] / pb["word_bytes"] == pytest.approx(2.0)
    assert pb["q_f32_bytes"] / (pb["word_bytes"] + pb["scale_bytes"]) > 1.9
    assert pb["f32_bytes"] / pb["bytes"] > 1.9
    q8 = param_bytes(quantize_params(params, QuantConfig(fmt="p8e2")))
    assert q8["q_f32_bytes"] / q8["word_bytes"] == pytest.approx(4.0)
    assert 0.0 < weight_golden_zone(qp) <= 1.0


def test_quant_matmul_backends_agree():
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((40, 24)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((6, 40)), jnp.float32)
    yx = quant_matmul(x, quantize_leaf(
        _leaf(w), QuantConfig(fmt="p16e1", backend="xla")))
    yp = quant_matmul(x, quantize_leaf(
        _leaf(w), QuantConfig(fmt="p16e1", backend="pallas")))
    # pallas also rounds the activations to the lattice — close, not
    # bitwise
    assert float(jnp.linalg.norm(yx - yp) / jnp.linalg.norm(yx)) < 1e-3


# --------------------------------------------------------------------------
# quantized forward through every family
# --------------------------------------------------------------------------

FAMILY_ARCHS = ["qwen2-0.5b", "granite-moe-1b-a400m", "mamba2-780m",
                "zamba2-2.7b", "gemma3-12b", "whisper-tiny",
                "internvl2-26b"]


def _tiny_batch(cfg, b=2, s=8, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (b, s)), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.full((b, cfg.enc_seq, cfg.d_model), 0.1,
                                   jnp.float32)
    if cfg.family == "vlm":
        batch["vis"] = jnp.full((b, cfg.vis_tokens, cfg.d_model), 0.1,
                                jnp.float32)
    return batch


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_quantized_prefill_every_family(arch):
    cfg = get_tiny_config(arch, policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _tiny_batch(cfg)
    ref = forward_prefill(params, batch, cfg)
    out = forward_prefill(
        quantize_params(params, QuantConfig(fmt="p16e1")), batch, cfg)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert np.isfinite(np.asarray(out)).all()
    assert rel < 0.02, (arch, rel)


# --------------------------------------------------------------------------
# scanned prefill == per-token loop (the dispatch-cost fix)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-780m"])
def test_prefill_scan_bit_identical_to_loop(arch):
    cfg = get_tiny_config(arch, policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 7), 0, cfg.vocab))
    c1, t1, p1 = prefill(params, cfg, toks, 32)
    c2, t2, p2 = prefill_loop(params, cfg, toks, 32)
    assert p1 == p2 and np.array_equal(np.asarray(t1), np.asarray(t2))
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# engine: allocator, batched == sequential, engine == generate
# --------------------------------------------------------------------------

def test_page_pool_allocator():
    cfg = get_tiny_config("qwen2-0.5b", policy="f32")
    spec = PagedKVSpec(page_size=4, n_pages=9, max_batch=2, max_pages=4,
                      fmt="p16e1")
    pool = PagePool(cfg, spec)
    assert len(pool.free) == 8                  # page 0 reserved
    pool.alloc_row(0, 3)
    assert pool.pages_in_use() == 3
    assert not pool.can_alloc(6)
    # positional order: linear index grows with position inside a page
    li = [pool.linear_index(0, t) for t in range(8)]
    assert li[1] == li[0] + 1 and li[5] == li[4] + 1
    # positions past the allocation hit the out-of-bounds drop sentinel
    assert pool.linear_index(0, 12) == spec.n_pages * spec.page_size
    pool.free_row(0)
    assert pool.pages_in_use() == 0 and len(pool.free) == 8
    with pytest.raises(AssertionError):
        pool.alloc_row(0, 9)


def _run_engine(params, cfg, reqs, *, max_inflight, kv_fmt,
                max_batch=3):
    eng = Engine(params, cfg, max_batch=max_batch, page_size=8,
                 max_seq=64, kv_fmt=kv_fmt, max_inflight=max_inflight)
    return eng.run([dataclasses.replace(r) for r in reqs]), eng


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-780m"])
@pytest.mark.parametrize("kv_fmt", [None, "p16e1"])
def test_engine_batched_bit_identical_to_sequential(arch, kv_fmt):
    """The acceptance gate: continuous-batched decode over paged posit
    KV produces bit-identical tokens to one-request-at-a-time decode
    through the same engine."""
    cfg = get_tiny_config(arch, policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if kv_fmt is not None:
        params = quantize_params(params, QuantConfig(fmt="p16e1"))
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (4 + 3 * i,))
                    .astype(np.int32),
                    max_new=5 + i) for i in range(4)]
    batched, _ = _run_engine(params, cfg, reqs, max_inflight=3,
                             kv_fmt=kv_fmt)
    seq, _ = _run_engine(params, cfg, reqs, max_inflight=1,
                         kv_fmt=kv_fmt)
    assert set(batched) == set(seq) == {0, 1, 2, 3}
    for rid in batched:
        assert np.array_equal(batched[rid], seq[rid]), rid


def test_engine_matches_generate():
    """f32 engine output == the dense-cache greedy decode for a dense
    arch (same cache semantics once the ring never wraps)."""
    cfg = get_tiny_config("qwen2-0.5b", policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab))
    out, _ = _run_engine(
        params, cfg, [Request(rid=0, prompt=prompt[0], max_new=8)],
        max_inflight=1, kv_fmt=None)
    ref = generate(params, cfg, prompt, max_new=8,
                   cache_len=Engine(params, cfg, max_batch=3, page_size=8,
                                    max_seq=64).spec.s_gather)
    assert np.array_equal(out[0], ref[0])


def test_engine_page_pressure_queues_and_drains():
    """More requests than pages: admission waits for frees, everything
    still completes, and pages fully recycle."""
    cfg = get_tiny_config("qwen2-0.5b", policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, max_batch=2, page_size=8, max_seq=32,
                 n_pages=5, kv_fmt="p16e1")
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, (6,))
                    .astype(np.int32), max_new=4) for i in range(5)]
    out = eng.run(reqs)
    assert set(out) == set(range(5))
    assert all(len(v) == 4 for v in out.values())
    assert eng.pool.pages_in_use() == 0


def test_engine_eos_stops_early():
    cfg = get_tiny_config("qwen2-0.5b", policy="f32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, max_batch=2, page_size=8, max_seq=64)
    base = eng.run([Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                            max_new=8)])
    toks = [int(t) for t in base[0]]
    # eos = the first token from the third on that was not generated
    # before it, so the run must stop exactly there (greedy decoding of
    # random weights may repeat a token)
    i = next(i for i in range(2, len(toks)) if toks[i] not in toks[:i])
    eos = toks[i]
    eng2 = Engine(params, cfg, max_batch=2, page_size=8, max_seq=64)
    out = eng2.run([Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                            max_new=8, eos_id=eos)])
    assert [int(t) for t in out[0]] == toks[:i + 1]


def test_tiny_configs_are_tiny():
    for arch in FAMILY_ARCHS:
        cfg = tiny_config(arch)
        assert cfg.vocab <= 128 and "tiny" in cfg.name
        assert cfg.n_layers == get_tiny_config(arch).n_layers
