"""Chip benchmark for the CFL system: CFL rounds of the paper CNN and
multi-tenant granite serving, driven by the data files in this directory.

Run one cell from the checkout root:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
