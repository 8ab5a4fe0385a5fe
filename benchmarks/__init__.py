"""The repository's benchmark: one command runs one cell once.

Everything here is the yardstick and belongs to no PR that claims a
gain: traffic generation, the reduction from traces, spans and counters
to metrics, the table of peaks, the operation and byte counts of each
kernel, each configuration's plain reference and the comparison that
decides ``correct``. From the program it takes only the system under
test. See ``benchmarks/README.md``.
"""
