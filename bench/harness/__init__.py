"""The benchmark harness: finds a cell's configuration, traffic, limits and
metric readers by name, drives the system under test on the chip, and
reduces what it measured to the metrics named in ``BENCHMARK.json``."""
