"""Chip benchmark of the PICE serving system (see BENCHMARK.json and PERF.md).

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell on the accelerator it is started on.
"""
