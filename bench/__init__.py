r"""On-chip benchmark of the serve path: one command, cells driven by data.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Each cell, configuration, traffic mix, driver and per-layer metric lives in
a file of its own under this directory and is found by the name
``BENCHMARK.json`` gives it; see ``bench/harness.py``.
"""
