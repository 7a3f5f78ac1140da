"""The on-chip benchmark of apex-tpu: ``python3 benchmarks/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>``.  See ``BENCHMARK.json`` for
the cells and metrics and ``PERF.md`` for why each exists."""
