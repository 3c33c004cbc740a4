"""Chip benchmark of this repository: one cell of ``BENCHMARK.json`` per
run, on a TPU, driving the program's own serve and train entry points.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Configurations (``configs/<name>.json``), traffic mixes
(``traffic/<name>.json``) and per-layer metric readers
(``metrics/<name>.py``) are found by the names in ``BENCHMARK.json``, so
a new cell is a new file and a new entry, never an edit.
"""
