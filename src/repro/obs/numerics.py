"""positscope numerics: posit-value telemetry computed from posit words
with pure jittable integer ops (no host decode of individual elements).

The paper's accuracy claim is a statement about WHERE values sit on the
posit regime/fraction trade-off: Posit(nbits, es) keeps its maximal
fraction width (``fmt.max_frac_bits``) only while the regime field is
shortest, i.e. for regime exponent k in {0, -1} — equivalently
|x| in [2^-(2^es), 2^(2^es)), the **golden zone** ([1/16, 16) for
p32e2, [1/4, 4) for p16e1/p8e0, [1/16, 16) for p8e2).  These collectors
measure that occupancy, plus the regime-width and scale (power-of-two
exponent) histograms, rounding/sticky events on the encode path, and
quire limb-carry counts — the evidence layer behind
``error_eval.golden_zone_study``.

Two call shapes:

* ``collect_numerics(words, fmt)`` / ``encode_round_stats(x, fmt)`` /
  ``quire_carry_stats(limbs)`` — jitted, return device scalars/arrays;
  usable standalone or from inside larger jitted telemetry bodies.
* ``record_numerics`` / ``emit_factor_steps`` — host-side, gate on
  ``active(...)`` or an open collector and push results into the open
  ``obs.scoped()`` collectors.

``active(*arrays)`` is the zero-cost gate used by every instrumented
library entry point: it is False when no collector is open OR when any
input is a tracer (the caller is itself being traced into an outer jit),
so the disabled path never adds an op to any lowered program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import posit
from repro.core.formats import P32E2, PositFormat
from repro.launch.mesh import replicated
from repro.obs import metrics as _metrics

_I64 = jnp.int64


def is_concrete(*arrays) -> bool:
    """True iff none of ``arrays`` is a JAX tracer."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def active(*arrays) -> bool:
    """The instrumentation gate: a collector is open AND the inputs are
    concrete (so running an obs-variant program cannot perturb an outer
    trace).  Resolved entirely at the Python level."""
    return bool(_metrics._STACK) and is_concrete(*arrays)


def golden_zone_bounds(fmt: PositFormat = P32E2) -> tuple[float, float]:
    """[lo, hi) magnitude band where ``fmt`` keeps its maximal fraction
    width (regime exponent k in {0, -1}): [2^-(2^es), 2^(2^es))."""
    return float(2.0 ** -(1 << fmt.es)), float(2.0 ** (1 << fmt.es))


def step_stats(words, fmt: PositFormat = P32E2) -> dict:
    """Small per-stage summary (traceable; all outputs are scalars):
    golden-zone occupancy, mean regime width, zero/NaR counts.  This is
    the payload the obs-variant factorization bodies emit per block step
    — cheap enough to compute for every panel/trailing update."""
    p = jnp.asarray(words, jnp.int32).ravel()
    is_zero, is_nar, _, scale, _ = posit.decode(p, fmt)
    es = fmt.es
    finite = ~(is_zero | is_nar)
    k = scale >> es
    reg_len = jnp.clip(jnp.where(k >= 0, k + 2, 1 - k), 2, fmt.nbits - 1)
    golden = finite & (k >= -1) & (k <= 0)
    nfin = jnp.maximum(jnp.sum(finite.astype(jnp.int64)), 1)
    return {
        "n": jnp.int64(p.size),
        "zero": jnp.sum(is_zero.astype(jnp.int64)),
        "nar": jnp.sum(is_nar.astype(jnp.int64)),
        "golden_frac": jnp.sum(golden.astype(jnp.float64)) / nfin,
        "regime_mean": (jnp.sum(jnp.where(finite, reg_len, 0)
                                .astype(jnp.float64)) / nfin),
    }


@functools.partial(jax.jit, static_argnames=("fmt",))
def collect_numerics(words, fmt: PositFormat = P32E2) -> dict:
    """Full posit-word telemetry of an array of ``fmt`` words:

    * ``regime_hist[w]`` — count of finite words whose regime field
      (run + terminator, as stored) is ``w`` bits wide, w in [2, nbits-1]
    * ``scale_hist[scale + max_scale]`` — count per power-of-two scale
      (the posit "exponent" histogram, fixed log2 bucketing by
      construction)
    * ``golden_frac`` / ``zero`` / ``nar`` / ``regime_mean`` — as in
      ``step_stats``

    Pure int ops on the decoded fields; jitted with ``fmt`` static.
    """
    p = jnp.asarray(words, jnp.int32).ravel()
    is_zero, is_nar, _, scale, _ = posit.decode(p, fmt)
    es = fmt.es
    finite = ~(is_zero | is_nar)
    k = scale >> es
    reg_len = jnp.clip(jnp.where(k >= 0, k + 2, 1 - k), 2, fmt.nbits - 1)
    one = finite.astype(jnp.int32)
    regime_hist = jnp.zeros((fmt.nbits,), jnp.int32).at[
        jnp.where(finite, reg_len, 0)].add(one, mode="drop")
    off = jnp.clip(scale + fmt.max_scale, 0, 2 * fmt.max_scale)
    scale_hist = jnp.zeros((2 * fmt.max_scale + 1,), jnp.int32).at[
        jnp.where(finite, off, 0)].add(one, mode="drop")
    out = step_stats(words, fmt)
    out["regime_hist"] = regime_hist
    out["scale_hist"] = scale_hist
    return out


@functools.partial(jax.jit, static_argnames=("fmt",))
def encode_round_stats(x, fmt: PositFormat = P32E2) -> dict:
    """Rounding-event / sticky-bit counters for encoding f64 carrier
    values into ``fmt`` — the same field dataflow as
    ``posit.chain_round`` (the repo's one encode path), recomputed here
    so the production encode stays untouched:

    * ``total``     — finite nonzero inputs
    * ``rounded``   — in-range inputs whose encode drops nonzero bits
                      (the encoded value differs from the input)
    * ``sticky``    — inputs with sticky bits below the kept+guard field
    * ``saturated`` — inputs clamped to ±maxpos / ±minpos
    """
    x = jnp.asarray(x, jnp.float64).ravel()
    nbits, es = fmt.nbits, fmt.es
    is_nan = jnp.isnan(x) | jnp.isinf(x)
    is_zero = (x == 0.0) & ~is_nan
    tiny = ~is_nan & ~is_zero & (jnp.abs(x) < np.float64(2.0 ** -1022))
    ax = jnp.abs(jnp.where(is_nan | is_zero | tiny, 1.0, x))
    mant, ex = jnp.frexp(ax)
    scale = ex.astype(_I64) - 1
    R = mant * np.float64(1 << 29)
    q = jnp.floor(R)
    sticky = R != q
    frac = q.astype(_I64) & ((_I64(1) << 28) - 1)

    k = scale >> es
    e = scale - (k << es)
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)
    ef = (_I64(1) << (es + 28)) | (e << 28) | frac
    d = jnp.clip(29 + es + reg_len - nbits, 1, es + 28)
    dropped = ef & ((_I64(1) << d) - 1)

    over = scale >= fmt.max_scale
    under = (scale < -fmt.max_scale) | tiny
    finite = ~(is_nan | is_zero)
    in_range = finite & ~over & ~under
    rounded = in_range & ((dropped != 0) | sticky)
    return {
        "total": jnp.sum(finite.astype(jnp.int64)),
        "rounded": jnp.sum(rounded.astype(jnp.int64)),
        "sticky": jnp.sum((in_range & sticky).astype(jnp.int64)),
        "saturated": jnp.sum((finite & (over | under)).astype(jnp.int64)),
    }


@jax.jit
def quire_carry_stats(limbs) -> dict:
    """Lazy-carry telemetry of redundant radix-2^32 quire limb state
    ((..., L) int64, repro.quire layout): run the canonical propagation
    sweep and count limb positions that release a nonzero carry — the
    cross-limb traffic an in-kernel quire implementation would pay.
    Returns per-position counts (``per_limb``, shape (L,)) + the total.
    """
    limbs = jnp.asarray(limbs, jnp.int64)
    L = limbs.shape[-1]
    carry = jnp.zeros(limbs.shape[:-1], jnp.int64)
    counts = []
    for j in range(L):
        v = limbs[..., j] + carry
        carry = v >> 32
        counts.append(jnp.sum((carry != 0).astype(jnp.int64)))
    per_limb = jnp.stack(counts)
    return {"per_limb": per_limb, "total": jnp.sum(per_limb)}


# --------------------------------------------------------------------------
# host-side recorders (no-ops unless a collector is open)
# --------------------------------------------------------------------------

def _hist_to_dict(arr, offset: int = 0) -> dict[int, int]:
    a = np.asarray(arr)
    return {int(i) + offset: int(v) for i, v in enumerate(a) if int(v)}


def record_numerics(name: str, words, fmt: PositFormat = P32E2):
    """Collect + record full word telemetry under ``name.*``; returns the
    stats dict (or None on the disabled path)."""
    if not active(words):
        return None
    st = collect_numerics(replicated(words), fmt)
    _metrics.gauge(f"{name}.golden_zone", st["golden_frac"])
    _metrics.gauge(f"{name}.regime_mean", st["regime_mean"])
    _metrics.inc(f"{name}.words", st["n"])
    _metrics.inc(f"{name}.nar", st["nar"])
    _metrics.observe_hist(f"{name}.regime_width",
                          _hist_to_dict(st["regime_hist"]))
    _metrics.observe_hist(f"{name}.scale",
                          _hist_to_dict(st["scale_hist"], -fmt.max_scale))
    return st


def emit_factor_steps(name: str, tel) -> None:
    """Flush a blocked-factorization collect-variant telemetry list
    (one dict of ``step_stats`` payloads per block step, keyed by stage:
    "panel" / "update") into the open collectors as a ``name.step``
    series plus summary gauge/counter — shared by the decomp and qr
    obs-variant drivers."""
    if not _metrics._STACK:
        return
    for i, step in enumerate(tel):
        row = {"step": i}
        for stage, st in step.items():
            row[f"{stage}_golden"] = st["golden_frac"]
            row[f"{stage}_regime_mean"] = st["regime_mean"]
            row[f"{stage}_nar"] = st["nar"]
        _metrics.record(f"{name}.step", **row)
    if tel:
        _metrics.gauge(f"{name}.last_panel.golden_zone",
                       tel[-1]["panel"]["golden_frac"])
    _metrics.inc(f"{name}.calls")


def golden_zone_fraction(words, fmt: PositFormat = P32E2) -> float:
    """Host convenience: golden-zone occupancy of an array of words
    (fraction of finite nonzero words with regime exponent k in
    {0, -1}).  Independent of the collector state."""
    return float(step_stats(jnp.asarray(words, jnp.int32), fmt)
                 ["golden_frac"])
