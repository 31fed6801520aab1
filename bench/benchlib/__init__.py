"""The chip benchmark's own code: loading cells, traffic, weights, the
float32 reference, trace reduction and metric arithmetic.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own under ``bench/`` and is found by the name
``BENCHMARK.json`` gives it; this package holds only what they share.
"""
