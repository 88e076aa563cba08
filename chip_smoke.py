#!/usr/bin/env python3
"""Run the posit(32,2) LAPACK main path on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases 1 and 2
    python chip_smoke.py --chips 4   # a 2x2 mesh: the distributed path only
    python chip_smoke.py --rehearse N
        # phases 1 and 2 at order N on whatever platform JAX has (on a CPU
        # the Pallas kernel runs in interpret mode); prints every number but
        # never the result line

Phase 1 (bit identity) reproduces on the chip the p32e2 golden pins of
tests/test_formats.py: the hashes and the recipe of their inputs live
there, and the test checks the same pins on a CPU.  The pinned
factorizations and solves ran on the f64-accumulating ``xla_quire``
backend, which a TPU cannot run exactly; ``quire_exact`` gives the same
words and runs here instead.  The ``pallas_split3`` pin is also the check
that the compiled kernel's f32 dots round as the CPU's do (sequential FMA
chains in k order, formed on the VPU; kernels/posit_gemm.py).

Phase 2 (full size) factors and solves the paper's section 5.1 inputs
(error_eval.make_general / make_spd, sigma = 1, x = ones / sqrt(n),
b = A x in binary64) at n = 4096 in p32e2 with the Pallas GEMM doing every
trailing update: rgetrf + rgetrs, rpotrf + rpotrs and rgesv_ir.  Each
entry point is called twice.  The first calls of all phases run side by
side in threads, so XLA compiles their programs in parallel; the second
call of each is timed alone (``run_s``), and ``compile_s`` is the first
call less ``run_s``.  Both end in ``block_until_ready``.  The
backward error |b - A x| / |b| is computed on the host in numpy f64 by
``error_eval._backward_error``, next to binary32's (numpy/scipy LAPACK
sgesv / spotrf on the host).  BOUNDS states what each must meet.

``--chips 4`` runs only the distributed path on a 2x2 ``make_grid_mesh``:
pdgemm in both schedules, p_rgetrf, p_rpotrf and p_rgesv_ir, each compared
word for word with the single-device call on the same chip.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; a failed check raises and exits non-zero.  With
no TPU the script exits 1 before any phase.  Everything runs in this one
process.  The compile cache is the one JAX_COMPILATION_CACHE_DIR names,
or else ``.jax_cache/`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from itertools import zip_longest
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Phase-2 bounds on the relative backward error |b - A x| / |b|: twice the
# errors of a CPU run of this script at the same n (`--rehearse 4096`:
# lu 1.402e-05, cholesky 1.573e-07, rgesv_ir 2.176e-09; binary32 4.58e-08
# and 3.64e-07).  Posit words are the same on a CPU and a TPU, so are
# these errors; the factor 2 only absorbs host-side f64 summation order.
BOUNDS = {"lu": 2.8e-5, "cholesky": 3.2e-7, "rgesv_ir": 4.4e-9}

N_FULL = 4096             # phase-2 matrix order
NB = 256                  # factorization block = trailing-update K
N_DIST, NB_DIST = 512, 128  # --chips 4: a 4x4 grid of blocks on 2x2 chips
BACKEND = "pallas_split3"


def _first_calls(calls: dict) -> dict:
    """Make each named call's first run (trace, compile, run) in a thread
    of its own, so XLA compiles the programs side by side; return
    {name: (result, seconds)}.  An exception in any call is re-raised."""
    import jax

    def one(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    with ThreadPoolExecutor(max_workers=len(calls)) as pool:
        futures = {name: pool.submit(one, fn) for name, fn in calls.items()}
        return {name: f.result() for name, f in futures.items()}


def _warm(label, fn, first_s):
    """Run ``fn`` again (compiled by now) and print both times."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    run_s = time.perf_counter() - t0
    print(f"{label}: compile_s={first_s - run_s:.6f} run_s={run_s:.6f} "
          f"(first call {first_s:.6f})", flush=True)
    return out


def phase1_calls(first):
    """The p32e2 golden pins as three waves of calls (later waves reuse
    the factorizations and the solution of earlier ones) and the check of
    their words."""
    sys.path.insert(0, str(ROOT / "tests"))
    import test_formats as golden
    from repro.kernels.ops import rgemm
    from repro.lapack import decomp, refine, solve

    ap, sp, bp = golden.golden_words()
    qe = "quire_exact"
    wave1 = {
        "rgemm_quire_exact": lambda: rgemm(ap, ap, backend=qe),
        "rgemm_pallas_split3": lambda: rgemm(ap, ap, backend="pallas_split3"),
        "rgemm_faithful": lambda: rgemm(ap, ap, backend="faithful"),
        "rgemm_ab1": lambda: rgemm(ap, ap, sp, alpha=-1.0, beta=1.0,
                                   backend=qe),
        "rpotrf": lambda: decomp.rpotrf(sp, nb=16, gemm_backend=qe),
        "rgetrf": lambda: decomp.rgetrf(ap, nb=16, gemm_backend=qe),
    }
    wave2 = {
        "rgetrs_q": lambda: solve.rgetrs(*first["rgetrf"][0], bp,
                                         quire=True),
        "rgesv_ir": lambda: refine.rgesv_ir(ap, bp, iters=2, nb=16,
                                            gemm_backend=qe)[0],
        "rposv_ir": lambda: refine.rposv_ir(sp, bp, iters=2, nb=16,
                                            gemm_backend=qe)[0],
    }
    wave3 = {"residual": lambda: refine.residual_quire(
        ap, first["rgesv_ir"][0][0], bp, first["rgesv_ir"][0][1])}

    def check():
        want = golden.GOLDEN_P32
        got = {k: golden._h(*first[k][0]) if isinstance(first[k][0], tuple)
               else golden._h(first[k][0]) for k in (*wave1, *wave2, *wave3)}
        for name, digest in got.items():
            mark = "==" if digest == want[name] else "!="
            print(f"phase1 {name}: {digest} {mark} pin {want[name]}",
                  flush=True)
        bad = [k for k, v in got.items() if v != want[k]]
        assert not bad, f"phase 1: words differ from the pins: {bad}"
        print(f"phase1: {len(got)} of {len(got)} pins reproduced",
              flush=True)

    return (wave1, wave2, wave3), check


def phase2_calls(first, n: int, on_tpu: bool):
    """Factor and solve at order n with the Pallas trailing update: the
    calls in two waves, and the timed warm runs plus the checks."""
    import jax
    import scipy.linalg
    from repro.core import posit
    from repro.lapack import decomp, error_eval, refine, solve

    to_words = jax.jit(posit.from_float64_bits)
    to_bits = jax.jit(posit.to_float64_bits)

    def host64(words):
        return np.asarray(to_bits(words)).view(np.float64)

    t0 = time.perf_counter()
    x_sol = np.full((n,), 1.0 / np.sqrt(n))
    a64 = error_eval.make_general(n, 1.0, seed=0)
    s64 = error_eval.make_spd(n, 1.0, seed=0)
    b64, bs64 = a64 @ x_sol, s64 @ x_sol
    a_p, b_p, s_p, bs_p = jax.block_until_ready([
        to_words(np.ascontiguousarray(x).view(np.int64))
        for x in (a64, b64, s64, bs64)])
    print(f"phase2 n={n} nb={NB} backend={BACKEND} "
          f"setup_s={time.perf_counter() - t0:.6f}", flush=True)

    def res(name):                         # a first call's result
        return first[f"{name}@{n}"][0]

    calls = {
        "rgetrf": lambda: decomp.rgetrf(a_p, nb=NB, gemm_backend=BACKEND),
        "rgetrs": lambda: solve.rgetrs(*res("rgetrf"), b_p),
        "rpotrf": lambda: decomp.rpotrf(s_p, nb=NB, gemm_backend=BACKEND),
        "rpotrs": lambda: solve.rpotrs(res("rpotrf"), bs_p),
        "rgesv_ir": lambda: refine.rgesv_ir(a_p, b_p, nb=NB,
                                            gemm_backend=BACKEND)[0],
    }
    wave1 = {f"{k}@{n}": calls[k] for k in ("rgetrf", "rpotrf")}
    wave2 = {f"{k}@{n}": calls[k] for k in ("rgetrs", "rpotrs", "rgesv_ir")}

    def check():
        out = {k: _warm(k, calls[k], first[f"{k}@{n}"][1]) for k in calls}
        text = decomp._rgetrf_jit.lower(a_p, nb=NB, gemm_backend=BACKEND
                                        ).compile().as_text()
        kernel = "tpu_custom_call" in text
        print(f"rgetrf compiled program holds the Pallas kernel "
              f"(tpu_custom_call): {kernel}", flush=True)

        berr = error_eval._backward_error
        x32 = np.linalg.solve(a64.astype(np.float32), b64.astype(np.float32))
        e32_lu = berr(a64, x32.astype(np.float64), b64)
        c32 = scipy.linalg.cho_factor(s64.astype(np.float32), lower=True)
        y32 = scipy.linalg.cho_solve(c32, bs64.astype(np.float32))
        e32_ch = berr(s64, y32.astype(np.float64), bs64)
        xh, xl = out["rgesv_ir"]
        rows = [("lu (rgetrf+rgetrs)", berr(a64, host64(out["rgetrs"]), b64),
                 e32_lu, BOUNDS["lu"]),
                ("cholesky (rpotrf+rpotrs)",
                 berr(s64, host64(out["rpotrs"]), bs64), e32_ch,
                 BOUNDS["cholesky"]),
                ("rgesv_ir (pair)", berr(a64, host64(xh) + host64(xl), b64),
                 e32_lu, BOUNDS["rgesv_ir"])]
        for name, e, e32, bound in rows:
            print(f"backward_error {name}: posit={e!r} binary32={e32!r} "
                  f"bound={bound!r} {'ok' if e <= bound else 'OUT OF BOUND'}",
                  flush=True)
        assert all(e <= bound for _, e, _, bound in rows), (
            "phase 2: a backward error is out of its bound")
        assert kernel or not on_tpu, "rgetrf ran without the compiled kernel"

    return (wave1, wave2), check


def dist_calls(first, n: int, nb: int):
    """The distributed path on a 2x2 mesh and its single-device twins, in
    two waves, and the word-for-word check."""
    import jax
    from repro.core import posit
    from repro.dist import (distribute, make_grid_mesh, p_rgesv_ir,
                            p_rgetrf, p_rpotrf, pdgemm)
    from repro.kernels.ops import rgemm
    from repro.lapack import decomp, error_eval, refine

    mesh = make_grid_mesh(2, 2)
    to_words = jax.jit(posit.from_float64_bits)
    a64 = error_eval.make_general(n, 1.0, seed=1)
    s64 = error_eval.make_spd(n, 1.0, seed=1)
    b64 = a64 @ np.full((n,), 1.0 / np.sqrt(n))
    a_p, s_p, b_p = (to_words(np.ascontiguousarray(x).view(np.int64))
                     for x in (a64, s64, b64))
    ad, sd = distribute(a_p, mesh, nb=nb), distribute(s_p, mesh, nb=nb)
    per_dev = {s.device.id: s.data.nbytes for s in ad.data.addressable_shards}
    print(f"dist: grid 2x2 n={n} nb={nb} bytes of A per device {per_dev}",
          flush=True)
    qe = "quire_exact"

    def p_lu(a):
        lu, piv = p_rgetrf(a, gemm_backend=BACKEND)
        return lu.gather(), piv

    wave1 = {
        "pdgemm owner-computes": lambda: pdgemm(ad, ad, backend=BACKEND
                                                ).gather(),
        "rgemm pallas": lambda: rgemm(a_p, a_p, backend=BACKEND),
        "pdgemm k_split": lambda: pdgemm(ad, ad, backend=qe,
                                         k_split=True).gather(),
        "rgemm quire": lambda: rgemm(a_p, a_p, backend=qe),
        "p_rgetrf": lambda: p_lu(ad),
        "rgetrf": lambda: decomp.rgetrf(a_p, nb=nb, gemm_backend=BACKEND),
        "p_rpotrf": lambda: p_rpotrf(sd, gemm_backend=BACKEND).gather(),
        "rpotrf": lambda: decomp.rpotrf(s_p, nb=nb, gemm_backend=BACKEND),
    }
    wave2 = {
        "p_rgesv_ir": lambda: p_rgesv_ir(ad, b_p, gemm_backend=BACKEND)[0],
        "rgesv_ir": lambda: refine.rgesv_ir(a_p, b_p, nb=nb,
                                            gemm_backend=BACKEND)[0],
    }

    def check():
        def same(x, y):
            return all(np.array_equal(np.asarray(u), np.asarray(v))
                       for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
        pairs = [("pdgemm owner-computes", "rgemm pallas"),
                 ("pdgemm k_split", "rgemm quire"), ("p_rgetrf", "rgetrf"),
                 ("p_rpotrf", "rpotrf"), ("p_rgesv_ir", "rgesv_ir")]
        for name in (*wave1, *wave2):
            print(f"dist {name}: first call {first[name][1]:.6f}s",
                  flush=True)
        oks = []
        for dist, single in pairs:
            ok = same(first[dist][0], first[single][0])
            oks.append(ok)
            print(f"dist {dist}: words identical to one device ({single}): "
                  f"{ok}", flush=True)
        assert all(oks), "distributed words differ from one device"

    return (wave1, wave2), check


def _run_waves(first: dict, parts) -> None:
    """Run the waves of several parts side by side, wave by wave, into
    ``first``: a wave's calls may read the results of the waves before."""
    for waves in zip_longest(*parts, fillvalue={}):
        merged = {}
        for wave in waves:
            merged.update(wave)
        first.update(_first_calls(merged))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", type=int, metavar="N")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.rehearse is None:
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device_kind: {dev.device_kind}", flush=True)
    print(f"device_count: {len(devices)}", flush=True)

    t0 = time.perf_counter()
    first = {}
    if args.chips == 4:
        assert len(devices) >= 4, f"--chips 4 needs 4 devices: {devices}"
        parts = [dist_calls(first, args.rehearse or N_DIST, NB_DIST)]
    else:
        parts = [phase1_calls(first),
                 phase2_calls(first, args.rehearse or N_FULL, on_tpu)]
    _run_waves(first, [waves for waves, _ in parts])
    for _, check in parts:
        check()
    print(f"total_s: {time.perf_counter() - t0:.6f}", flush=True)
    if args.rehearse is not None:
        print("rehearsal: no result line", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
