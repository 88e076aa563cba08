"""positscope — numerics + performance observability (DESIGN.md §10).

What a profiler trace of a plain call shows, with nothing opened::

    jax.profiler.start_trace(logdir)
    (x_hi, x_lo), _ = refine.rgesv_ir(a_p, b_p)
    jax.profiler.stop_trace()

* on the device: every op of the main path carries its layer's scope in
  its op metadata (``posit.panel``, ``posit.swap``, ``posit.trsm``,
  ``posit.update``, ``posit.sweep``, ``posit.quire_sweep``,
  ``posit.quire_residual``, ``posit.pair_update``; ``obs.scopes``), and
  the Pallas GEMM runs as ``posit_gemm_<format>``;
* on the host: one span per public entry point (``posit.rgetrf``,
  ``posit.rpotrf``, ``posit.rgemm``, ``posit.rgesv_ir``,
  ``posit.rposv_ir``), on the device trace's clock.

A collector is only needed for the numerics::

    from repro import obs

    with obs.scoped() as m:
        (x_hi, x_lo), _ = refine.rgesv_ir(a_p, b_p)
    print(m.to_json())                      # counters/gauges/hists/series
    m.save_chrome_trace("solve_trace.json") # open in Perfetto

Four parts:

* ``obs.scopes``  — the table of layer scopes above;
* ``obs.metrics`` — process-local registry (counters, gauges, fixed-log2
  histograms, series) behind the ``scoped()`` collector stack;
* ``obs.trace``   — nested wall-clock spans -> Chrome trace_event JSON,
  always forwarded to ``jax.profiler.TraceAnnotation``;
* ``obs.numerics``— jittable posit-word telemetry (golden-zone occupancy,
  regime/scale histograms, encode rounding/sticky counters, quire
  limb-carry counts) + the ``active()`` gate the instrumented library
  code uses.

With no collector open every recorder is a Python-level no-op and the
instrumented hot paths dispatch the exact same jitted programs (pinned
in tests/test_obs.py); the scopes are op metadata only.
"""
from repro.obs.metrics import (Collector, enabled, gauge, inc, observe,
                               observe_hist, record, scoped)
from repro.obs.numerics import (active, collect_numerics, encode_round_stats,
                                golden_zone_bounds, golden_zone_fraction,
                                is_concrete, quire_carry_stats,
                                record_numerics, step_stats)
from repro.obs import scopes
from repro.obs.trace import span

__all__ = [
    "Collector", "enabled", "gauge", "inc", "observe", "observe_hist",
    "record", "scoped", "scopes", "span", "active", "collect_numerics",
    "encode_round_stats", "golden_zone_bounds", "golden_zone_fraction",
    "is_concrete", "quire_carry_stats", "record_numerics", "step_stats",
]
