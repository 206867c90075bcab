"""The benchmark of the swarm round on a TPU: cells, traffic, reference and
metric readers, driven by ``BENCHMARK.json`` at the repository's root.

Run one cell with ``python3 swarmbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""
