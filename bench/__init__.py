"""Chip benchmark of the recommender stack (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once on the chips of the machine it is started on.
"""
