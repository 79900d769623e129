"""Chip benchmark of the committing peer, driven by data.

``BENCHMARK.json`` at the checkout root names the cells. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file
of its own, found by name:

  bench/configs/<config>.json   deployment sizes, guarantees, path
  bench/traffic/<traffic>.json  parameters of the one general generator
  bench/metrics/<metric>.py     a reader with ``read(ctx) -> float | None``

``bench/run.py`` is the command; ``bench/reference.py`` is the plain
sequential reference that decides ``correct``.
"""
