"""On-chip benchmark of the WoW index, driven by the data files beside it.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once; see ``run.py``.
"""
