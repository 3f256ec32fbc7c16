"""Plain references the benchmark compares the service with."""
