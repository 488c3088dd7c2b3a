"""Plain references of the benchmark's architectures."""
