"""The chip benchmark of the analytical-CV server: cells, traffic, reference and metrics.

Run one cell as ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the checkout root
names the cells, and every configuration, traffic mix, limit set and
metric reader is a file of its own under this directory, found by name.
"""
