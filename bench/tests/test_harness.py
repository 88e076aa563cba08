"""The harness's contract: no TPU, no result; the result line's keys."""
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run_cli(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "general.gemm",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _results(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    return [json.loads(ln) for ln in lines]


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert _results(p.stdout) == []
    assert "TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert _results(p.stdout) == []


def _last_line(run, argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run.main(argv) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["general.gemm", "general.lu_solve"])
def test_result_line_has_only_the_contract_keys(small_cell, workload):
    run = small_cell(32)
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    res = _last_line(run, ["--workload", workload, "--seed", str(2**31 + 7),
                           "--seconds", "0.3", "--trace", "0"])
    assert list(res) == KEYS + ["checks"]          # checks come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for check in res["checks"].values():
        assert set(check) == {"value", "limit"}


def test_traced_cpu_run_reads_no_device_share(small_cell):
    run = small_cell(32)
    res = _last_line(run, ["--workload", "general.gemm", "--seed", "3",
                           "--seconds", "0.2", "--trace", "1"])
    assert list(res) == KEYS + ["breakdown", "checks"]
    # no TPU ops in a CPU trace: only the host-clock metric is read
    assert set(res["metrics"]) == {"dispatch_s"}
    assert res["device"]["busy_s"] == 0.0
