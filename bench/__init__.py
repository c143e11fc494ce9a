"""The chip benchmark: harness, traffic, configurations and yardstick."""
