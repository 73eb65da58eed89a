"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its model configuration in
``bench/configs/<config>.json`` and the architecture module it names
(``bench/<reference>.py``, ``bench/model_ref.py`` by default: weights,
reference and work counts), its traffic mix in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  See ``PERF.md`` for what each measures.
"""
