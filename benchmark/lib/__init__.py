"""The benchmark's yardstick: traffic, window, trace reduction, peaks, check."""
