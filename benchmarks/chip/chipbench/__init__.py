"""The chip benchmark's yardstick: discovery, traffic loops, the plain
reference, the comparison that decides ``correct``, trace reduction and
model-FLOP counts.  ``benchmarks/chip/run.py`` is the entry point."""
