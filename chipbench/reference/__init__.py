"""Plain reference implementation of the benchmarked models."""
