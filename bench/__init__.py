"""On-chip serving benchmark of the Griffin serving engine.

``python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
model configuration, traffic mix or per-layer metric is a file of its own
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by
the name the manifest gives it; a configuration's block (its reference,
weight leaves and GEMMs) is a module under ``bench/blocks``, found by the
name its file gives.
"""
