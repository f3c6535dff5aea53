"""Chip benchmark of the GLIN serving path (``python3 bench/run.py``).

Everything that measures lives here: traffic generation, the plain
reference that decides ``correct``, the reduction from profiler traces and
server counters to metrics, the table of device peaks, and the work counts
of the kernels. From the program it takes only the system under test
(``SpatialIndex`` behind ``SpatialQueryServer``), its counters and its
kernel names.
"""
