"""The benchmark: the yardstick later PRs are measured with and may not change.

``python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. See ``benchmark/README.md``.
"""
