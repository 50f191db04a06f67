"""Chip benchmark for cGES structure learning: seconds per learned DAG.

Run one cell with ``python3 perfbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; see ``run.py``.
"""
