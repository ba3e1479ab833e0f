"""The on-chip benchmark of the anytime kNN / CF serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell needs is found
by name under this directory: ``configs/<config>.json`` (sizes and the
limits of the correctness check), ``apps/<app>.py`` (how the data and the
servable are made), ``reference/<app>.py`` (the plain reference),
``traffic/<traffic>.json`` (the arrival mix), ``metrics/<metric>.py`` (one
reader per per-layer metric) and ``kernels/<kernel>.py`` (a kernel's
algorithmic operations and bytes).
"""
