"""The on-chip benchmark: ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` (cells in ``BENCHMARK.json``)."""
