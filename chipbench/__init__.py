"""The chip benchmark: open-loop SCLS serving measured on one TPU.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON line.  Everything that decides a number lives here: the traffic
generator, the percentile and window arithmetic, the operation and byte
counts, the table of peaks, the trace reduction and the plain reference
that decides ``correct``.  From the program under ``src/`` the benchmark
takes only the serving stack, its counters and its jitted programs' names.
"""
