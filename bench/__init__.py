"""On-chip benchmark of the Morpher flow: cells, references and metric readers.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``; see ``bench/run.py``.
"""
