"""Benchmark harness for the five BASELINE.json configurations.

Run: ``python -m benchmarks.run [config1|config2|config3|config4|config5|all]``

Each config prints one JSON line ({"metric", "value", "unit",
"vs_baseline", ...}) plus config-specific detail fields.  Configs 2 and 5
report device rates and fail without a TPU.
"""
