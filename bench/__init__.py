"""On-chip serving benchmark, driven by ``BENCHMARK.json`` at the repo root.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell once on the chips of the machine it is started on.  A cell,
a configuration, a block family, a traffic mix and a per-layer metric are
each found by name in files of their own (``cells/``, ``configs/``,
``blocks/``, ``traffic/``, ``metrics/``), so a new one is new files plus a
``BENCHMARK.json`` entry.
"""
