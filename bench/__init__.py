"""On-chip benchmark of the ITR triple store: cells as data.

``BENCHMARK.json`` at the checkout root names the cells; each cell is one
configuration (``bench/configs/<config>.json``) under one traffic mix
(``bench/traffic/<mix>.json``). Per-layer metrics are readers in
``bench/layers/<metric>.py``, device programs are described by
``bench/kernels/<name>.json``, and data generators live in
``bench/generators/<generator>.py``. ``bench/run.py`` runs one cell.
"""
