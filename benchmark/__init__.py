"""The benchmark of the served ``POST /parse`` path.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line. Everything a cell needs is found by name: its
configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<mix>.json`` (with the arrival process and the line sources it
names in ``traffic/arrivals/<kind>.py`` and ``traffic/sources/<name>.py``)
and each metric's reader in ``metrics/<metric>.py``.
"""
