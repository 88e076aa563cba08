#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration (a file
under bench/configs/) and a traffic mix (bench/traffic/<traffic>.json).
The traffic names its entry, bench/entries/<entry>.py, which builds the
operands, calls the system under test (``src/repro``) and maps what a call
returns onto the reference's checks (bench/reference.py).  Each per-layer
metric is read by bench/metrics/<name>.py.  Nothing here names a cell.

One run: set-up (imports, device, data from the seed, one warm call,
which compiles or loads the compile cache), then a closed loop of one
caller for ``--seconds``: calls back to back on the operand sets in turn,
each ending in ``block_until_ready``; a call started in the window is
finished and counted.  With ``--trace 1`` a few more calls run under the
profiler and the per-layer metrics are read from that trace.  Then the
outputs of the window's calls are checked against the binary64 reference
on the host, and the result line is printed.

    python bench/run.py --rehearse cpu --workload <name> [--n N]
        end to end at order N on the CPU (the Pallas kernel interpreted):
        prints every number, never a result line
    python bench/run.py --rehearse compile --workload <name>
        compiles the cell's programs at full size for a described v5e,
        runs nothing

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's compile cache lives in .jax_cache/ at the
root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(BENCH))

import reference as ref  # noqa: E402
import trace_reduce  # noqa: E402


class NoDevice(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    """The cell, its configuration, traffic, entry and limits, by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", cells)]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", cells)]
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "limits": limits,
            "per_layer": per_layer, "end_to_end": end_to_end,
            "entry": importlib.import_module(f"entries.{traffic['entry']}")}


def start_jax(chips: int, require_tpu: bool):
    """Import JAX with the checkout's compile cache; check the devices.
    libtpu's own logs, which go to a fixed /tmp path, are switched off."""
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX has "
                       f"{len(devices)} {devices[0].platform} device(s)")
    return jax, devices


def rehearsal_size(cfg: dict, n: int) -> dict:
    """The configuration at order n, with at least two blocks."""
    return {**cfg, "n": n, "nb": min(cfg["nb"], n // 2)}


def program_format(cfg: dict, control: bool = False):
    from repro.core.formats import PositFormat
    f = cfg["control"] if control else cfg
    return PositFormat(f["nbits"], f["es"])


class Cell:
    """One cell's operands and call, built from the seed."""

    def __init__(self, c: dict, seed: int, fmt, jax):
        self.c, self.fmt, self.jax = c, fmt, jax
        t = c["traffic"]
        self.host = c["entry"].operand_sets(c["cfg"], seed,
                                            t["operand_sets"])
        self.dev = [c["entry"].to_device(h, fmt) for h in self.host]
        self.call = c["entry"].make_call(c["cfg"], fmt, t)
        jax.block_until_ready(self.dev)

    def one(self, i: int):
        """Call on operand set i mod sets; return (set, outputs, dispatch
        seconds, total seconds)."""
        s = i % len(self.dev)
        t0 = time.perf_counter()
        out = self.call(self.dev[s])
        t1 = time.perf_counter()
        self.jax.block_until_ready(out)
        return s, out, t1 - t0, time.perf_counter() - t0

    def window(self, seconds: float):
        """The closed loop: calls back to back until ``seconds`` have
        passed; the last call started inside the window is finished."""
        calls = []
        t0 = time.perf_counter()
        while True:
            calls.append(self.one(len(calls)))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return calls, elapsed

    def check(self, calls, sample_seed: int) -> tuple[dict, int]:
        """Check the calls' outputs against the reference.  Every call
        gets the cheap numbers; one call per operand set, drawn from the
        seed, also the full ones.  Returns (worst of each number, calls
        failed)."""
        entry, limits = self.c["entry"], self.c["limits"]
        rng = ref.rng_for(sample_seed, 1000)
        full = set()
        for s in range(len(self.host)):
            idx = [i for i, call in enumerate(calls) if call[0] == s]
            if idx:
                full.add(int(rng.choice(idx)))
        worst, failed = {}, 0
        for i, (s, out, _, _) in enumerate(calls):
            host_out = {k: np.asarray(v) for k, v in out.items()}
            nums = entry.check(self.host[s], host_out, self.fmt, i in full,
                               self.c["traffic"])
            merge_worst(worst, nums)
            failed += any(not (v <= limits[k]["limit"])
                          for k, v in nums.items())
        return worst, failed


def merge_worst(worst: dict, nums: dict) -> None:
    """Keep in ``worst`` the largest of each number; no number (NaN)
    is the worst of all."""
    for k, v in nums.items():
        v, w = float(v), worst.get(k, -math.inf)
        worst[k] = v if math.isnan(v) or v > w else w


def device_info(jax, devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def traced_calls(cell: Cell, n_calls: int, first: int):
    """Run ``n_calls`` more calls under the profiler; return the reduced
    trace, the calls and the host window [start, end] in trace time."""
    jax = cell.jax
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        calls = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0           # host spans only, no Python
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                for i in range(first, first + n_calls):
                    with jax.profiler.TraceAnnotation("bench.call"):
                        calls.append(cell.one(i))
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return trace, calls


def read_per_layer(c: dict, ctx: dict) -> dict:
    """The cell's per-layer metrics that its readers find; none from the
    device trace where the trace lost events."""
    out = {}
    whole = trace_reduce.complete(ctx["trace"])
    if not whole:
        log(f"bench: the device trace lost its last "
            f"{trace_reduce.lost_tail_s(ctx['trace']):.3f} s (the profiler "
            f"keeps a bounded number of events): device-trace metrics are "
            f"left out")
    for m in c["per_layer"]:
        if m["source"] == "device_trace" and not whole:
            continue
        reader = importlib.import_module(f"metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> int:
    c = load_cell(args.workload)
    chips = c["cell"]["chips"]
    jax, devices = start_jax(chips, require_tpu=args.rehearse is None)
    if args.n:
        c["cfg"] = rehearsal_size(c["cfg"], args.n)
    fmt = program_format(c["cfg"])
    log(f"bench: {args.workload} seed={args.seed} device="
        f"{devices[0].device_kind} x{len(devices)} n={c['cfg']['n']}; "
        f"devices ready at {time.perf_counter() - T_START:.3f} s")

    cell = Cell(c, args.seed, fmt, jax)
    t_data = time.perf_counter() - T_START
    cell.one(0)                                    # warm: compile or load
    setup_s = time.perf_counter() - T_START
    log(f"bench: data ready at {t_data:.3f} s, set-up {setup_s:.3f} s")

    calls, elapsed = cell.window(args.seconds)
    call_s = elapsed / len(calls)
    metrics, extra = {}, {}
    if args.trace:
        dispatch_s = [d for _, _, d, _ in calls]
        trace, tcalls = traced_calls(cell, c["traffic"]["trace_calls"],
                                     len(calls))
        calls += tcalls
        ctx = {"trace": trace, "calls": len(tcalls), "cfg": c["cfg"],
               "entry": c["entry"], "kind": devices[0].device_kind,
               "dispatch_s": dispatch_s,
               "layers": trace_reduce.load_layers(BENCH / "metrics")}
        metrics = read_per_layer(c, ctx)
        busy_s, window_s = trace_reduce.busy_and_window(trace)
        extra = {"busy_s": busy_s, "window_s": window_s}
        breakdown = trace_reduce.breakdown(trace)
    device = {**device_info(jax, devices, chips), **extra}
    del cell.dev

    worst, failed = cell.check(calls, args.seed)
    limits = c["limits"]
    checks = {k: {"value": _finite(worst.get(k, math.nan)),
                  "limit": limits[k]["limit"]} for k in limits}
    correct = failed == 0 and all(ch["value"] <= ch["limit"]
                                  for ch in checks.values())
    if not args.trace:
        metrics["call_s"] = {"value": call_s, "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        if "berr_solve" in checks:
            metrics["accuracy_digits"] = {
                "value": -math.log10(checks["berr_solve"]["value"]),
                "unit": "digits"}
    wanted = {m["name"] for m in (c["per_layer"] if args.trace
                                  else c["end_to_end"])}
    metrics = {k: v for k, v in metrics.items() if k in wanted}
    log(f"bench: {len(calls)} calls, call_s {call_s!r}, metrics "
        f"{json.dumps(metrics)}")
    for k, ch in checks.items():
        log(f"check {k}: {ch['value']!r} limit {ch['limit']!r} "
            f"{'ok' if ch['value'] <= ch['limit'] else 'FAIL'}")
    if args.rehearse is not None:
        log("rehearsal: no result line")
        return 0
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def _finite(v: float) -> float:
    """A compared number as JSON can carry it: no number (NaN, from NaR
    words) or an infinite one reads as the largest float, which fails
    every limit."""
    return v if math.isfinite(v) else sys.float_info.max


def rehearse_compile(args) -> int:
    """Compile the cell's programs at full size for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    c = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    fmt = program_format(c["cfg"])
    n = c["cfg"]["n"]
    host = {k: v for k, v in c["entry"].operand_sets(
        {**c["cfg"], "n": 8}, 0, 1)[0].items()}
    shapes = {k: jax.ShapeDtypeStruct((n,) * v.ndim, np.int32,
                                      sharding=one_chip)
              for k, v in host.items()}
    call = c["entry"].make_call(c["cfg"], fmt, c["traffic"])
    t0 = time.perf_counter()
    compiled = jax.jit(call).lower(shapes).compile()
    text = compiled.as_text()
    log(f"compiled {args.workload} ({fmt}) for a described v5e in "
        f"{time.perf_counter() - t0:.1f} s; tpu_custom_call: "
        f"{'tpu_custom_call' in text}; memory: {compiled.memory_analysis()}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", choices=("cpu", "compile"))
    ap.add_argument("--n", type=int, help="matrix order (rehearsals only)")
    args = ap.parse_args(argv)
    if args.n and args.rehearse is None:
        ap.error("--n is for rehearsals only")
    try:
        if args.rehearse == "compile":
            return rehearse_compile(args)
        return run(args)
    except NoDevice as e:
        log(f"bench: {e}")
        return 3


if __name__ == "__main__":
    sys.exit(main())
