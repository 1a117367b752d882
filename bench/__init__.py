"""The benchmark harness: see bench/run.py."""
