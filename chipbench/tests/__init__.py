"""Tests of the benchmark harness (CPU, no chip)."""
