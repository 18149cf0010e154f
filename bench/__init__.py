"""On-chip benchmark of the fusion compiler.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that defines a
measurement lives here and nowhere in the program: the traffic
generator, the float64 references, the bytes and operations a call
requires, the table of peaks and the reduction of a device trace.
"""
