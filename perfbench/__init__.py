"""The benchmark of ray_tpu: BENCHMARK.json at the root names what is here."""
