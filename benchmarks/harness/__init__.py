"""The benchmark's yardstick: device stamp and peaks, clocks, the trace
reduction, the loader that finds a cell's files by name, the result line."""
