"""The chip benchmark: the yardstick every change to the library is measured by.

Entry point: ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. See ``bench/README.md`` for how cells, configurations,
traffic mixes and metrics are added as files.
"""
