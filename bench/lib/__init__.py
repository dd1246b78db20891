"""Shared code of the benchmark harness: the yardstick that later changes
measure against (discovery of cells by name, peaks, FLOP and byte counts,
trace reduction, compile accounting, the comparison that decides
``correct``)."""
