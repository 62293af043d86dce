"""The benchmark: one command runs one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See benchmark/README.md. Everything that decides a number lives here
(traffic, window, trace reduction, peaks, kernel bytes, the checks behind
`correct`); from the program it takes the system under test, its spans,
its counters and its kernel names.
"""
