"""Chip benchmark of the served SQLcached path (see BENCHMARK.json)."""
