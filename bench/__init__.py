"""The on-chip benchmark of this repository (see ``BENCHMARK.json``).

Everything the benchmark needs sits under this directory and is found by
name: model configurations in ``configs/``, traffic mixes in
``traffic/``, one driver per traffic ``driver`` in ``drivers/``, one
reader per per-layer metric in ``metrics/``, the limits of each cell's
correctness comparison in ``limits/``. Run one cell with::

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
