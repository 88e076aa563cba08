"""ABFT overhead benchmark: checksum-protected vs unprotected paths.

Three families, each with **bit-identity asserted before any timing**
(the protected path's whole contract is that fault-free words equal the
unprotected words exactly, and recovered words equal fault-free words
exactly — a mismatch is a bug, not noise):

* ``rgemm_ft``   — quire-checksummed GEMM vs plain ``rgemm``,
                   fault-free and with one injected word flip (the
                   1-fault row times detection + one retry)
* ``rgetrf_ft``  — protected host-stepped blocked LU vs the frozen
                   single-dispatch ``rgetrf`` (acceptance target:
                   <= 1.3x fault-free overhead at n=512)
* ``pdgemm_ft``  — strip-checksummed distributed GEMM vs ``pdgemm`` on
                   a forced-host-device grid (subprocess child, the
                   bench_dist.py pattern)

``--soak N`` (the nightly fault-injection soak) runs N seeded random
injections per site across every protected driver and ASSERTS 100%
detection with bit-identical recovery; the soak tally rides along as
rows so the artifact records the evidence.

Writes ``BENCH_ft.json`` (schema: {meta, results: [{name, config,
t_old_ms (unprotected), t_new_ms (protected), speedup, overhead,
identical}]}) — merged by merge_bench.py next to the other BENCH files.
Read ``overhead`` (= protected/unprotected) directly; ``speedup`` keeps
the shared merge schema (old/new ratio, < 1 here by construction).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench_decomp import _identical, _time_pair
from repro import ft
from repro.core import posit as P
from repro.kernels.ops import rgemm
from repro.lapack import decomp, qr


def _posit_matrix(rng, shape, lo=-4, hi=4):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(lo, hi, shape))
    return P.from_float64(jnp.asarray(x))


def _row(name, config, t_old, t_new, identical, results):
    r = {"name": name, "config": config, "t_old_ms": round(t_old, 3),
         "t_new_ms": round(t_new, 3), "speedup": round(t_old / t_new, 3),
         "overhead": round(t_new / t_old, 3), "identical": identical}
    results.append(r)
    flag = "" if identical else "  << MISMATCH"
    print(f"{name:<12} {config:<30} plain {t_old:8.1f}ms  ft {t_new:8.1f}ms"
          f"  {r['overhead']:5.2f}x overhead{flag}", flush=True)
    assert identical, f"{name} {config}: protected path not bit-identical"
    return r


def bench_rgemm_ft(results, quick, reps):
    rng = np.random.default_rng(0)
    n = 96 if quick else 256
    a, b = _posit_matrix(rng, (n, n)), _posit_matrix(rng, (n, n))
    ref = rgemm(a, b)
    got, _, rep = ft.rgemm_ft(a, b)
    assert rep.detections == 0
    t_old, t_new = _time_pair(lambda: rgemm(a, b),
                              lambda: ft.rgemm_ft(a, b)[0], reps)
    _row("rgemm_ft", f"n={n} fault-free", t_old, t_new,
         _identical(got, ref), results)

    plan = ft.make_plan(1, "rgemm.out", size=n * n)
    got, _, rep = ft.rgemm_ft(a, b, plan=plan)
    assert rep.detections == 1
    t_old, t_new = _time_pair(lambda: rgemm(a, b),
                              lambda: ft.rgemm_ft(a, b, plan=plan)[0], reps)
    _row("rgemm_ft", f"n={n} 1-fault", t_old, t_new,
         _identical(got, ref), results)


def bench_rgetrf_ft(results, quick, reps):
    rng = np.random.default_rng(1)
    n, nb = (96, 32) if quick else (512, 64)
    a = _posit_matrix(rng, (n, n))
    ref = decomp.rgetrf(a, nb=nb)
    lu, piv, rep = decomp.rgetrf_ft(a, nb=nb)
    assert rep.detections == 0
    t_old, t_new = _time_pair(lambda: decomp.rgetrf(a, nb=nb),
                              lambda: decomp.rgetrf_ft(a, nb=nb)[0], reps)
    _row("rgetrf_ft", f"n={n} nb={nb} fault-free", t_old, t_new,
         _identical((lu, piv), ref), results)

    plan = ft.make_plan(2, "rgetrf.step", size=n * nb, steps=n // nb)
    lu, piv, rep = decomp.rgetrf_ft(a, nb=nb, plan=plan)
    assert rep.detections >= 1
    t_old, t_new = _time_pair(
        lambda: decomp.rgetrf(a, nb=nb),
        lambda: decomp.rgetrf_ft(a, nb=nb, plan=plan)[0], reps)
    _row("rgetrf_ft", f"n={n} nb={nb} 1-fault", t_old, t_new,
         _identical((lu, piv), ref), results)


_CHILD = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, {bench_dir!r})
from bench_decomp import _time_pair, _identical
from repro.core import posit as P
from repro.dist import distribute, make_grid_mesh, pdgemm
from repro.dist.pblas import pdgemm_ft

quick = {quick!r}
p, q = {grid!r}
mesh = make_grid_mesh(p, q)
n = 96 if quick else 192
reps = 3 if quick else 6
rng = np.random.default_rng(0)
def pm(shape):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(-4, 4, shape))
    return P.from_float64(jnp.asarray(x))

a, b = pm((n, n)), pm((n, n))
ad, bd = distribute(a, mesh, 32), distribute(b, mesh, 32)
ref = pdgemm(ad, bd)
got, rep = pdgemm_ft(ad, bd)
assert rep.detections == 0
ident = _identical(got.gather(), ref.gather())
t_old, t_new = _time_pair(lambda: pdgemm(ad, bd).data,
                          lambda: pdgemm_ft(ad, bd)[0].data, reps)
rows = [{{"name": "pdgemm_ft", "config": f"n={{n}} fault-free",
          "devices": p * q, "grid": f"{{p}}x{{q}}",
          "t_old_ms": round(t_old, 3), "t_new_ms": round(t_new, 3),
          "speedup": round(t_old / t_new, 3),
          "overhead": round(t_new / t_old, 3), "identical": ident}}]
print("ROWS_JSON " + json.dumps(rows))
"""


def bench_pdgemm_ft(results, quick, bench_dir):
    devices = 4 if quick else 8
    grid = (2, 2) if quick else (2, 4)
    code = _CHILD.format(bench_dir=bench_dir, quick=quick, grid=grid)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a CPU rehearsal on forced host devices: the parent may hold the
    # chip, so the child must not reach for it
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.abspath(os.path.join(bench_dir, "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"pdgemm_ft child failed:\n{r.stdout[-2000:]}\n"
                           f"{r.stderr[-4000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROWS_JSON "):
            for row in json.loads(line[len("ROWS_JSON "):]):
                assert row["identical"], "pdgemm_ft not bit-identical"
                results.append(row)
                print(f"{row['name']:<12} {row['config']:<30} "
                      f"plain {row['t_old_ms']:8.1f}ms  "
                      f"ft {row['t_new_ms']:8.1f}ms  "
                      f"{row['overhead']:5.2f}x overhead", flush=True)
            return
    raise RuntimeError("pdgemm_ft child: no ROWS_JSON in output")


def soak(results, n_inject, quick):
    """N seeded random injections per site across every protected
    driver: ASSERTS 100% detection and bit-identical recovery, then
    records the tally as bench rows (the nightly fault-injection
    soak)."""
    rng = np.random.default_rng(3)
    n, nb = (48, 16) if quick else (96, 32)
    a = _posit_matrix(rng, (n, n))
    spd = rgemm(a, a, trans_b=True)
    tall = _posit_matrix(rng, (n, nb * 2))
    def run_rgemm(plan):
        c, _, rep = ft.rgemm_ft(a, a, plan=plan)
        return (c,), rep

    def run_qgemm(plan):
        c, _, rep = ft.quire_gemm_ft(a, a, plan=plan)
        return (c,), rep

    def run_getrf(plan):
        lu, piv, rep = decomp.rgetrf_ft(a, nb=nb, plan=plan)
        return (lu, piv), rep

    def run_potrf(plan):
        l, rep = decomp.rpotrf_ft(spd, nb=nb, plan=plan)
        return (l,), rep

    def run_geqrf(plan):
        r, tau, rep = qr.rgeqrf_ft(tall, nb=nb, plan=plan)
        return (r, tau), rep

    word_kinds = ("flip", "nar", "saturate")
    # site -> (runner, reference, lane count, steps, fault nbits, kinds);
    # limb-plane faults are bit flips only (nar/saturate are word-domain)
    cases = {
        "rgemm.out": (run_rgemm, (rgemm(a, a),), n * n, 1, 32, word_kinds),
        "rgemm.limbs": (run_qgemm,
                        (rgemm(a, a, backend="quire_exact"),),
                        n * n, 1, 64, ("flip",)),
        "rgetrf.step": (run_getrf, decomp.rgetrf(a, nb=nb),
                        n * nb, n // nb, 32, word_kinds),
        "rpotrf.step": (run_potrf, (decomp.rpotrf(spd, nb=nb),),
                        n * nb, n // nb, 32, word_kinds),
        "rgeqrf.step": (run_geqrf, qr.rgeqrf(tall, nb=nb),
                        n * nb, 2, 32, word_kinds),
    }
    for site, (run, ref, size, steps, nbits, kinds) in cases.items():
        injected = detected = recovered = 0
        for seed in range(n_inject):
            plan = ft.make_plan(seed, site, size=size, steps=steps,
                                kinds=kinds, nbits=nbits)
            out, rep = run(plan)
            injected += 1
            detected += 1 if rep.detections >= 1 else 0
            recovered += 1 if _identical(out, ref) else 0
        row = {"name": "soak", "config": f"{site} x{n_inject}",
               "injected": injected, "detected": detected,
               "recovered": recovered,
               "identical": detected == injected == recovered}
        results.append(row)
        print(f"soak {site:<14} injected {injected}  detected {detected}"
              f"  recovered {recovered}", flush=True)
        assert detected == injected, f"{site}: missed detections"
        assert recovered == injected, f"{site}: non-identical recovery"


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="small sizes / fewer reps (CI perf-smoke)")
    parser.add_argument("--soak", type=int, default=0, metavar="N",
                        help="also run N seeded injections per site and "
                             "assert 100%% detection (nightly)")
    parser.add_argument("--out", default="BENCH_ft.json")
    args = parser.parse_args(argv)
    reps = 3 if args.quick else 5
    bench_dir = os.path.dirname(os.path.abspath(__file__))

    results = []
    bench_rgemm_ft(results, args.quick, reps)
    bench_rgetrf_ft(results, args.quick, reps)
    bench_pdgemm_ft(results, args.quick, bench_dir)
    if args.soak:
        soak(results, args.soak, args.quick)

    payload = {
        "meta": {
            "bench": "bench_ft", "quick": args.quick,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "note": ("overhead = protected/unprotected wall-clock; "
                     "identity is the gate, timings are trajectory"),
        },
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(results)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
