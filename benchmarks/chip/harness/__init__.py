"""The chip benchmark's yardstick: everything that turns a workload named in
``BENCHMARK.json`` into one measured run, apart from the system under test.

Each configuration, traffic mix and metric lives in a file of its own under
``benchmarks/chip`` (``configs/``, ``traffic/``, ``metrics/``,
``reference/``, ``drivers/``); ``spec.resolve`` finds them by name.
"""
