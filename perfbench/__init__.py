"""Chip benchmark of this repository: one cell (model configuration under
one traffic mix) per run, driven by ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
