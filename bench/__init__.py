"""On-chip benchmark of the read mapper and LM serving (see BENCHMARK.json)."""
