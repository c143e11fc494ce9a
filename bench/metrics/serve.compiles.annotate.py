"""Programs built inside the window (bench/readers.py)."""
from bench.readers import window_compiles as read  # noqa: F401
