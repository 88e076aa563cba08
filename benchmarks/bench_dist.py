"""Distributed linear-algebra scaling sweep: 1/2/4/8 host devices.

For each device count D this script spawns a fresh interpreter with
``--xla_force_host_platform_device_count=D`` (the flag must precede
backend init) and, inside it:

1. **asserts bit-identity first** — ``pdgemm`` / ``p_rpotrf`` /
   ``p_rgetrf`` words equal the single-device ``rgemm`` / ``rpotrf`` /
   ``rgetrf`` words on the D-device grid (plus the 1x8 degenerate grid
   at D=8, per the acceptance criteria) — no timing is reported for a
   mismatching configuration;
2. times dist vs single-device with the **interleaved best-of-N**
   estimator (``bench_decomp._time_pair``): this box is 2 vCPUs with
   ±2x host drift, so alternating the two programs rep-by-rep is the
   only way the ratio means anything.

Writes ``BENCH_dist.json`` (schema: {meta, results: [{name, config,
devices, grid, t_single_ms, t_dist_ms, speedup, identical}]}) — uploaded
by CI perf-smoke next to BENCH_decomp.json.

Read the numbers as *trajectory data*: D forced host devices on 2 real
cores time-slice the same silicon, so wall-clock "speedup" here mostly
measures the dist schedule's overhead (gathers, masked updates), not
scaling; on a real multi-chip mesh the same program distributes the
O(n³) trailing work P*Q ways.  Identity is the acceptance gate.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys

def _grid_for(d: int) -> tuple[int, int]:
    """Most-square P x Q factoring of d (largest divisor <= sqrt(d))."""
    p = max(f for f in range(1, int(d ** 0.5) + 1) if d % f == 0)
    return p, d // p

_CHILD = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, {bench_dir!r})
from bench_decomp import _time_pair, _identical
from repro import obs
from repro.core import posit as P
from repro.kernels.ops import rgemm
from repro.lapack import decomp
from repro.dist import distribute, make_grid_mesh, pdgemm, p_rpotrf, p_rgetrf

quick = {quick!r}
devices = {devices!r}
p, q = {grid!r}
mesh = make_grid_mesh(p, q)
nb = 32
n = 96 if quick else 192
reps = 3 if quick else 6
rng = np.random.default_rng(0)

def pm(shape, lo=-4, hi=4):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(lo, hi, shape))
    return P.from_float64(jnp.asarray(x))

rows = []
def row(name, config, single_fn, dist_fn, ident):
    assert ident, f"{{name}} {{config}}: dist path is not bit-identical"
    t_s, t_d = _time_pair(single_fn, dist_fn, reps)
    with obs.scoped() as m:            # un-timed observed re-run: the
        jax.block_until_ready(dist_fn())   # collective-byte counters
    rows.append({{"name": name, "config": config, "devices": devices,
                 "grid": [p, q], "t_single_ms": round(t_s, 3),
                 "t_dist_ms": round(t_d, 3),
                 "speedup": round(t_s / t_d, 3), "identical": True,
                 "metrics": m.bench_block()}})

# pdgemm
a, b = pm((n, n)), pm((n, n))
ad, bd = distribute(a, mesh, nb), distribute(b, mesh, nb)
for backend in ("xla_quire", "quire_exact"):
    ref = rgemm(a, b, backend=backend)
    got = pdgemm(ad, bd, backend=backend)
    row("pdgemm", f"{{n}}^3 nb={{nb}} {{backend}}",
        lambda: rgemm(a, b, backend=backend),
        lambda: pdgemm(ad, bd, backend=backend).data,
        _identical(got.gather(), ref))

# factorizations (xla_quire: the fast CPU trailing-update path)
g = rng.standard_normal((n, n))
sp = P.from_float64(jnp.asarray(g.T @ g + n * np.eye(n)))
gp = P.from_float64(jnp.asarray(g))
spd, gpd = distribute(sp, mesh, nb), distribute(gp, mesh, nb)
ref_l = decomp.rpotrf(sp, nb=nb)
got_l = p_rpotrf(spd)
row("p_rpotrf", f"n={{n}} nb={{nb}} xla_quire",
    lambda: decomp.rpotrf(sp, nb=nb), lambda: p_rpotrf(spd).data,
    _identical(got_l.gather(), ref_l))
ref_lu = decomp.rgetrf(gp, nb=nb)
got_lu = p_rgetrf(gpd)
row("p_rgetrf", f"n={{n}} nb={{nb}} xla_quire",
    lambda: decomp.rgetrf(gp, nb=nb),
    lambda: p_rgetrf(gpd)[0].data,
    _identical((got_lu[0].gather(), got_lu[1]), ref_lu))

if devices == 8:
    # acceptance: the 1x8 degenerate grid is also bit-identical
    m18 = make_grid_mesh(1, 8)
    ok = (_identical(pdgemm(distribute(a, m18, nb), distribute(b, m18, nb),
                            backend="quire_exact").gather(),
                     rgemm(a, b, backend="quire_exact"))
          and _identical(p_rpotrf(distribute(sp, m18, nb)).gather(), ref_l))
    assert ok, "1x8 grid not bit-identical"
    rows.append({{"name": "identity_1x8", "config": f"n={{n}} nb={{nb}}",
                 "devices": 8, "grid": [1, 8], "t_single_ms": 0.0,
                 "t_dist_ms": 0.0, "speedup": 1.0, "identical": True}})

print("ROWS_JSON " + json.dumps(rows))
"""


def run_child(devices: int, quick: bool, bench_dir: str):
    code = _CHILD.format(quick=quick, devices=devices,
                         grid=_grid_for(devices), bench_dir=bench_dir)
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
        raise RuntimeError(f"devices={devices} child failed:\n"
                           f"{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROWS_JSON "):
            return json.loads(line[len("ROWS_JSON "):])
    raise RuntimeError(f"devices={devices}: no ROWS_JSON in output")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="small sizes / fewer reps (CI perf-smoke)")
    parser.add_argument("--devices", default="1,2,4,8",
                        help="comma-separated host-device counts")
    parser.add_argument("--out", default="BENCH_dist.json")
    args = parser.parse_args(argv)
    bench_dir = os.path.dirname(os.path.abspath(__file__))

    results = []
    for d in (int(x) for x in args.devices.split(",")):
        rows = run_child(d, args.quick, bench_dir)
        for r in rows:
            results.append(r)
            print(f"{r['name']:<12} {r['config']:<26} D={r['devices']} "
                  f"grid={r['grid']}  single {r['t_single_ms']:8.1f}ms  "
                  f"dist {r['t_dist_ms']:8.1f}ms  {r['speedup']:5.2f}x",
                  flush=True)

    payload = {
        "meta": {"bench": "bench_dist", "quick": args.quick,
                 "platform": platform.platform(),
                 "python": platform.python_version(),
                 "note": ("host devices time-slice the same cores; "
                          "identity is the gate, timings are trajectory")},
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(results)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
