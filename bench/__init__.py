"""The benchmark of the GSI serving stack: one cell of ``BENCHMARK.json``
per run, ``python3 -m bench.run --workload <name> --seed <n> --seconds
<s> --trace <0|1>``.  Everything that measures lives here; the program
under test is ``src/repro``."""
