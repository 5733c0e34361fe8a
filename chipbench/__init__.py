"""chipbench: the benchmark of paddle-tpu on the TPU v5e.

`python3 -m chipbench.run --workload W --seed N --seconds S --trace 0|1`
runs one cell of `BENCHMARK.json`. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found by its name: `configs/<config>.json`, `traffic/<traffic>.json`,
`metrics/<metric>.json` (which names a reader in `readers/`), and the
driver a traffic mix names in `drivers/`. The yardstick lives here too:
the traffic generator, the weights, the reduction of a trace, the table
of peaks, the counts of operations and bytes, the plain references and
the comparison that decides `correct`. From the program the benchmark
takes the system under test, its counters and its kernel names.
"""
