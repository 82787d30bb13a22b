"""Shared pieces of the chip benchmark (``benchmarks/chip/run.py``).

Everything that turns a run into numbers lives here, apart from the system
under test: the peaks table, the FLOPs count, the trace reductions, the
seeded state and traffic, the plain reference's common operations and the
comparison that decides ``correct``.
"""
