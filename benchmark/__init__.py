"""The benchmark of vernemq-tpu: one cell, one run, one process tree.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` boots the broker in this process (the only one that
touches JAX), drives it over loopback TCP from JAX-free child processes,
and prints one JSON line. Everything that decides a number lives here,
under the directory ``BENCHMARK.json`` names in ``paths``: traffic
generation, the plain reference, the comparison that decides ``correct``,
the reduction from counters and the device trace to metrics, the table of
peaks. From the program it takes the system under test and its counters.
"""
