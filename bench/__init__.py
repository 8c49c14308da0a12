"""Chip benchmark of the partitioner: ``python3 bench/run.py --workload <cell>``.

Everything that defines a measurement lives here: the graph generators
(``data``), the plain reference and the comparison that decides
``correct`` (``reference``), the profiler-trace reduction (``trace``), the
table of peaks (``peaks.json``), one JSON file per deployment (``configs``)
and per traffic mix (``traffic``), and one reader per per-layer metric
(``layers``).  From the program it takes only the system under test
(``repro.core.partition``) and the names of its jitted functions; a traced
run also marks calls into its layers in the profiler's timeline
(``harness.instrument``).
"""
