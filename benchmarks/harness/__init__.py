"""The benchmark's yardstick: manifest lookup, traffic generation, metric
arithmetic, peaks, FLOP/byte functions, the plain reference and the trace
reduction. Nothing here imports flexflow_tpu; only `cells/` drives the
system under test."""
