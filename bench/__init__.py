"""On-chip benchmark of the SpotTune trial runtime (see BENCHMARK.json)."""
