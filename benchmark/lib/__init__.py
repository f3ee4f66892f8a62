"""The benchmark's own yardstick: data, oracle, traffic, load loop, trace
reduction.  Nothing here imports ``pilosa_tpu``."""
