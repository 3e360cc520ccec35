"""The benchmark of ray_tpu: cells, traffic, yardstick. See PERF.md.

Driven by data: ``BENCHMARK.json`` names cells, configurations and
metrics; the harness finds ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``layer_metrics/<metric>.py`` by name.
"""
