"""Shared yardstick of the benchmark: loading cells by name, traffic and
data generation, trace reduction, plain references, chip peaks."""
