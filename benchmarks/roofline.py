"""Roofline analysis over the dry-run records (spec: ROOFLINE ANALYSIS).

Reads experiments/dryrun/*.json (written by repro.launch.dryrun) and
derives, per (arch x shape x mesh):

    compute term    = HLO_FLOPs / peak_FLOPs          [s, per device]
    memory term     = HLO_bytes / HBM_bw              [s, per device]
    collective term = collective_bytes / link_bw      [s, per device]

cost_analysis / the HLO parse operate on the SPMD-partitioned per-device
module, so all three terms are already per-chip; the spec's (chips x peak)
denominator cancels.  MODEL_FLOPS uses 6*N_active*D (train), 2*N_active*D
(prefill), 2*N_active*B (decode) per device.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--dir experiments/dryrun]
Writes experiments/roofline.md and prints a CSV summary.

``--check-pdgemm`` runs the collective-accounting cross-check instead:
the static per-device byte plan (``pblas.pdgemm_collective_plan``), the
compiled-HLO parse (``hlo_analysis.collective_bytes``), and the runtime
obs counters (``repro.obs``) must agree kind-for-kind on a 2x2 grid for
both pdgemm schedules — three independent derivations of the roofline's
collective term, one report.  Spawns a 4-host-device child (the
XLA_FLAGS must precede backend init); exits nonzero on any mismatch.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np

PEAK_FLOPS = 197e12          # TPU v5e bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

_LEVERS = {
    "compute": "raise MXU utilization: larger per-device batch/seq tiles, "
               "fewer redundant (remat) flops",
    "memory": "fuse elementwise chains / increase arithmetic intensity "
              "(bigger tiles, bf16 everywhere, avoid spills)",
    "collective": "reshard to cut cross-chip traffic (more FSDP locality, "
                  "posit16-compressed wire formats, overlap with compute)",
}


def _param_counts(arch: str):
    from repro.configs import get_config
    from repro.models import init_params
    import functools
    cfg = get_config(arch)
    abstract = jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.PRNGKey(0))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(abstract))
    expert = 0
    if cfg.n_experts:
        def walk(t, inmoe=False):
            nonlocal expert
            if isinstance(t, dict):
                for k, v in t.items():
                    walk(v, inmoe or k in ("w_gate", "w_up", "w_down"))
            elif isinstance(t, (list, tuple)):
                for v in t:
                    walk(v, inmoe)
            elif hasattr(t, "shape") and inmoe:
                expert += int(np.prod(t.shape))
        walk(abstract["layers"])
        active = total - expert + expert * cfg.top_k / cfg.n_experts
    else:
        active = total
    return total, active, cfg


def model_flops(rec, active_params: float) -> float:
    """Useful model flops per device for this cell."""
    from repro.configs import cell_by_name
    cell = cell_by_name(rec["cell"])
    n_dev = rec["n_devices"]
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active_params * tokens / n_dev
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active_params * tokens / n_dev
    return 2.0 * active_params * cell.global_batch / n_dev   # decode


def analyze(dirpath: str):
    """NOTE on loop bodies: XLA cost_analysis counts a while-loop body
    ONCE regardless of trip count, and this framework scans over layer
    periods (compile-time O(period), the production design).  Raw HLO
    flops/bytes therefore undercount by ~n_layers/period for the scanned
    portion.  We report terms from the trip-count-corrected numbers
    (raw x n_periods — a slight overcount of the non-scanned epilogue,
    so raw and corrected bracket the truth) and keep the raw ratio
    column for visibility."""
    from repro.models.lm import period_of
    rows = []
    pc_cache = {}
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        rec = json.load(open(path))
        if rec.get("compressed") or rec.get("policy") not in (
                "default", None):
            continue
        arch = rec["arch"]
        if arch not in pc_cache:
            pc_cache[arch] = _param_counts(arch)
        total, active, cfg = pc_cache[arch]
        n_periods = cfg.n_layers // period_of(cfg)
        t_c = rec["flops"] * n_periods / PEAK_FLOPS
        t_m = rec["bytes_accessed"] * n_periods / HBM_BW
        coll = sum(rec["collective_bytes"].values())
        t_x = coll * n_periods / LINK_BW
        dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
                  key=lambda kv: kv[1])[0]
        mf = model_flops(rec, active)
        hlo_corr = rec["flops"] * n_periods
        ratio = mf / hlo_corr if hlo_corr > 0 else float("nan")
        t_model = mf / PEAK_FLOPS
        frac = t_model / max(t_c, t_m, t_x) if max(t_c, t_m, t_x) else 0.0
        hbm = (rec["argument_size_bytes"] or 0) + (rec["temp_size_bytes"]
                                                   or 0)
        rows.append({
            "arch": arch, "cell": rec["cell"], "mesh": rec["mesh"],
            "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
            "dominant": dom, "model_flops_ratio": ratio,
            "roofline_fraction": frac, "hbm_gib": hbm / 2 ** 30,
            "fits_hbm": hbm <= 16 * 2 ** 30,
            "lever": _LEVERS[dom],
        })
    return rows


def to_markdown(rows) -> str:
    out = ["| arch | cell | mesh | compute s | memory s | collective s | "
           "dominant | useful/HLO flops | roofline frac | HBM GiB | fits |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['cell']} | {r['mesh']} "
            f"| {r['t_compute_s']:.2e} | {r['t_memory_s']:.2e} "
            f"| {r['t_collective_s']:.2e} | {r['dominant']} "
            f"| {r['model_flops_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['hbm_gib']:.1f} | {'Y' if r['fits_hbm'] else 'N'} |")
    return "\n".join(out)


# --------------------------------------------------------------------------
# collective-accounting cross-check (static plan vs HLO vs runtime obs)
# --------------------------------------------------------------------------

_PDGEMM_CHECK = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core import posit
from repro.core.formats import P32E2
from repro.dist import layout, pblas
from repro.launch import hlo_analysis

n, nb = {n}, {nb}
mesh = jax.make_mesh((2, 2), ("row", "col"))
rng = np.random.default_rng(0)
a_p = posit.from_float64(jnp.asarray(rng.standard_normal((n, n))))
b_p = posit.from_float64(jnp.asarray(rng.standard_normal((n, n))))
A = layout.distribute(a_p, mesh, nb)
B = layout.distribute(b_p, mesh, nb)
lay = A.layout
sharding = jax.sharding.NamedSharding(mesh, pblas._SPEC)
c0 = jax.device_put(jnp.zeros((lay.p * lay.lm, lay.q * lay.ln), jnp.int32),
                    sharding)

out = []
for k_split, backend in ((False, "xla_quire"), (True, "quire_exact")):
    plan = pblas.pdgemm_collective_plan(lay, lay, k_split=k_split)
    hlo = hlo_analysis.collective_bytes(
        pblas._pdgemm_sharded.lower(
            A.data, B.data, c0, lay_a=lay, lay_b=lay, mesh=mesh,
            alpha=1.0, beta=0.0, backend=backend, k_split=k_split,
            fmt=P32E2).compile().as_text())
    with obs.scoped() as m:
        pblas.pdgemm(A, B, backend=backend, k_split=k_split)
    pre = "dist.pdgemm."
    runtime = {{k[len(pre):-len(".bytes")]: int(v)
               for k, v in m.to_dict()["counters"].items()
               if k.startswith(pre) and k.endswith(".bytes")}}
    out.append({{"schedule": "k_split" if k_split else "owner-computes",
                "backend": backend, "plan": plan, "hlo": hlo,
                "runtime": runtime}})
print("CHECK_JSON " + json.dumps(out))
"""


def check_pdgemm(n: int = 64, nb: int = 16) -> int:
    """Run the three-way pdgemm collective-byte cross-check on a 2x2
    grid (4 forced host devices, fresh interpreter) and print one
    roofline-style report.  Returns a process exit code."""
    code = _PDGEMM_CHECK.format(n=n, nb=nb)
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    # a CPU rehearsal on forced host devices: the parent may hold the
    # chip, so the child must not reach for it
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        print(f"check child failed:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}",
              file=sys.stderr)
        return 1
    rows = None
    for line in r.stdout.splitlines():
        if line.startswith("CHECK_JSON "):
            rows = json.loads(line[len("CHECK_JSON "):])
    if rows is None:
        print("no CHECK_JSON in child output", file=sys.stderr)
        return 1

    print(f"# pdgemm collective accounting, n={n} nb={nb}, 2x2 grid "
          "(per-device bytes)\n")
    print("| schedule | collective | plan B | HLO B | runtime B | agree |")
    print("|---|---|---|---|---|---|")
    ok = True
    for row in rows:
        kinds = sorted(set(row["plan"]) | set(row["hlo"])
                       | set(row["runtime"]))
        for kind in kinds:
            p = row["plan"].get(kind, 0)
            h = row["hlo"].get(kind, 0)
            u = row["runtime"].get(kind, 0)
            agree = p == h == u
            ok &= agree
            print(f"| {row['schedule']} | {kind} | {p} | {h} | {u} "
                  f"| {'Y' if agree else 'MISMATCH'} |")
        total = sum(row["plan"].values())
        print(f"| {row['schedule']} | **total** | {total} |  |  | "
              f"t_coll = {total / LINK_BW:.2e} s |")
    print(f"\n{'AGREE' if ok else 'MISMATCH'}: static plan vs compiled HLO "
          "vs runtime obs counters")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline.md")
    ap.add_argument("--check-pdgemm", action="store_true",
                    help="cross-check pdgemm collective bytes (plan vs "
                         "HLO vs runtime obs) on a 2x2 grid and exit")
    args = ap.parse_args(argv)
    if args.check_pdgemm:
        raise SystemExit(check_pdgemm())
    rows = analyze(args.dir)
    md = to_markdown(rows)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("# Roofline terms per (arch x shape x mesh)\n\n")
        f.write(f"Constants: {PEAK_FLOPS/1e12:.0f} TF/s bf16, "
                f"{HBM_BW/1e9:.0f} GB/s HBM, {LINK_BW/1e9:.0f} GB/s link. "
                "All terms are per-device seconds per step.\n\n")
        f.write(md + "\n")
    print(md)
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    print(f"\n# {len(rows)} cells; dominant-term counts: {doms}")
    worst = sorted(rows, key=lambda r: r["roofline_fraction"])[:5]
    print("# five worst roofline fractions:")
    for r in worst:
        print(f"#   {r['arch']} x {r['cell']} x {r['mesh']}: "
              f"{r['roofline_fraction']:.3f} ({r['dominant']}-bound)")


if __name__ == "__main__":
    main()
