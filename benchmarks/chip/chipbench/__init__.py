"""The chip benchmark's yardstick: traffic, reference, trace reduction.

Later changes to the program may not edit anything here; they add
configurations, traffic mixes, metric readers and work models as files of
their own, which ``bench.Bench`` finds by the names in ``BENCHMARK.json``.
"""
