"""The plain reference the benchmark compares the system with."""
