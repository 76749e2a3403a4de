"""The benchmark's own yardstick: traffic, peaks, operation and byte
counts, trace reduction, the wall-clock driver of the serving engine, and
the comparison that decides ``correct``."""
