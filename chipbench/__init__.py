"""chipbench: the repository's benchmark (see chipbench/README.md).

Everything the yardstick needs lives here: traffic generation, the plain
references, the table of peaks, the FLOP counts, the trace reduction and
the comparison that decides ``correct``. From ``hadoop_tpu`` it takes only
the system under test and its counters.
"""
