"""inferd_tpu.perf — the measurement subsystem.

The ROADMAP north star is "as fast as the hardware allows"; this package
is the part of the repo that says what the hardware allows and whether a
measurement is consistent with it:

  * roofline — analytic per-decode-step cost model (bytes + FLOPs) for any
    ModelConfig x quant mode x KV dtype x context x batch, against a
    chip-spec table: floor ms/step, ceiling tok/s, and the one audited
    definition of `hbm_roofline_frac` (bench.py's ad-hoc arithmetic
    re-derives from here — docs/PERF.md).
  * autotune — persistent per-(chip, shape, dtype) measurement registry
    consulted by the `auto` dispatches in ops/attention.py (kernel vs
    XLA) and ops/quant.py (int4 contraction scheme) when populated;
    bit-for-bit fallback to the frozen heuristics when cold.
    tools/sweep_attn.py --populate fills it from hardware.
  * gate     — perf regression gate over committed BENCH_*.json(l)
    artifacts: steady/e2e ordering, roofline-fraction regressions vs a
    prior artifact, and physical-impossibility (frac > 1) checks.

CLI: `python -m inferd_tpu.perf {report,check}` (see __main__).

No module in this package may initialize a JAX backend at import time
(tests/test_cli.py test_package_import_initializes_no_jax_backend).
"""
