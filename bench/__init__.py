"""On-chip benchmark of the Canny edge service (see BENCHMARK.json)."""
