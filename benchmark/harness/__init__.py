"""The benchmark's shared code: world, traffic, window, reference, check
and trace reduction.  Changed only by a benchmark PR."""
