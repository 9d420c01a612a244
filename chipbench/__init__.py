"""chipbench — the benchmark of tpu-hq on the chip.

One command runs one cell of BENCHMARK.json once, in one process that holds
the chip:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in BENCHMARK.json:
`configs/<config>.json`, `traffic/<traffic>.json` (which names its driver),
`metrics/<metric>.py`.  The yardstick (generators, plain references, the
trace reduction, the peaks table, the kernel's ops and bytes) lives here and
imports nothing of the program; the drivers take from the program only the
system under test and its spans and counters.
"""
