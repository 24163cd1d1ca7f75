"""SART serving benchmark: one cell (configuration x traffic mix) per run.

``bench/run.py`` is the entry point. The modules here are the yardstick:
traffic generation (``traffic``), seeded weights (``weights``), the engine
proxy and the load generator (``proxy``, ``loadgen``), the reduction of spans and
the device trace to metrics (``trace``, ``stats``), the work counts and the
table of peaks (``roofline``), and the comparison with the plain float32
reference that decides ``correct`` (``check``).
"""
