"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name: ``configs/<config>.json`` with its plain
reference ``configs/<config>.ref.py``, ``traffic/<traffic>.json`` driven by
``drive_<kind>.py``, and ``metrics/<metric>.py``.
"""
