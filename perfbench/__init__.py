"""The on-chip benchmark of lux_tpu.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once. Everything the
benchmark measures with lives here: the seeded generators, the traffic
drivers, the plain references, the work counts, the peaks table and the
reduction from a profiler trace to metrics. From ``lux_tpu`` it takes
only the system under test.
"""
