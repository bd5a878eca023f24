"""The benchmark's own code: it finds a cell's files by name, builds the
system under test, times it, reduces the trace and decides ``correct``.

Nothing here names a cell.  A cell of ``BENCHMARK.json`` names one file
under ``configs/`` and one under ``traffic/``; its limits sit in
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``.
"""
