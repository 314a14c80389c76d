"""Plain references the benchmark compares the program's outputs with."""
