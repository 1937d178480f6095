"""The on-chip benchmark of this repository (see ``BENCHMARK.json``).

Everything the benchmark needs sits under this directory and is found by
name: model configurations in ``configs/``, one module per architecture
class (a configuration's ``architectures[0]``) in ``archs/``, holding
the program's model configuration, the float32 reference and the counts
of operations and bytes, traffic mixes in ``traffic/``, one driver per
traffic ``driver`` in ``drivers/``, one reader per per-layer metric in
``metrics/``, the limits of each cell's correctness comparison in
``limits/``. Run one cell with::

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
