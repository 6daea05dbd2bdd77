"""The benchmark of distributedfft_tpu: one run of one cell per process
(``python3 perf/run.py --workload <cell> ...``). Everything that defines
a measurement lives here, apart from the program under test."""
