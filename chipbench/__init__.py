"""On-chip benchmark of the AWAPart serving path.

One run serves one cell of ``BENCHMARK.json`` (a configuration under a
traffic mix) on the chip and prints one JSON result line:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name: ``configs/<name>.json``,
``traffic/<name>.json`` (read by the generator in ``arrivals.py`` and
served by the loop ``kinds/<kind>.py``), ``metrics/<name>.py``.
"""
