"""The chip benchmark: one harness driven by ``BENCHMARK.json`` and data files.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell.  A cell names a configuration (``bench/configs/<name>.json``,
whose ``"arch"`` names its architecture's module ``bench/arch/<arch>.py``)
and a traffic mix (``bench/traffic/<name>.json``); its correctness limits are
in ``bench/limits/<cell>.json`` and each per-layer metric has a reader in
``bench/metrics/<metric>.py``.
"""
