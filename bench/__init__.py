"""The benchmark: harness, yardstick and plain references."""
