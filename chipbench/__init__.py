"""Chip benchmark of the repro solver stack (see ``BENCHMARK.json``).

Run one cell once with ``python chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout,
on a machine that holds the TPU chips the cell asks for.
"""
