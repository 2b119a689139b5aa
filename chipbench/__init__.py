"""The chip benchmark of the fabric simulator (see BENCHMARK.json).

``python -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the machine it is started on and prints
one JSON line. Everything that belongs to one configuration, traffic mix
or per-layer metric is a file of its own under ``configs/``,
``traffic/`` and ``metrics/``, found by the name ``BENCHMARK.json`` gives.
"""
