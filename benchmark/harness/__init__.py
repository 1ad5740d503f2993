"""The benchmark's own code: cell loading, the measured loop, the timing
arithmetic, the trace reduction, the peaks table and the FLOP arithmetic.
Nothing here imports from ``autodist_tpu``: the yardstick may not move with
the program it measures."""
