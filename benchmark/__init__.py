"""The repository's benchmark: one command, one cell, one run, one last line.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the trace reduction, the peaks table,
the FLOP and byte functions, the plain references and the comparison that
decides ``correct``. See PERF.md and BENCHMARK.json.
"""
