"""The plain reference implementations the benchmark compares against."""
