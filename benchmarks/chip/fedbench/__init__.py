"""The FedCore fleet-round chip benchmark.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own under ``benchmarks/chip``
(``configs/``, ``traffic/``, ``metrics/``, ``kernels/``, ``limits/``),
found by the name that ``BENCHMARK.json`` gives it.  This package holds
the general parts: loading those files, the population and data
generator, the run itself, the reduction of a profiler trace, the peaks
table and the comparison that decides ``correct``.
"""
