"""Plain references the benchmark compares the system under test with."""
