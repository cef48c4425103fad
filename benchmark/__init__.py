"""The benchmark: harness, yardstick and plain reference (see README.md)."""
