"""Input generators of the benchmark, independent of the program under test."""
