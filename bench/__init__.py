"""Benchmark harness of the serving path: ``python3 bench/run.py``.

Driven by data: ``BENCHMARK.json`` names each cell's configuration
(``bench/configs/<config>.json``, whose members name their architecture,
``bench/arch/<arch>.py``), traffic mix (``bench/traffic/<mix>.json``) and
per-layer metrics (``bench/metrics/<metric>.py``); adding one is adding a
file and a manifest entry.
"""
