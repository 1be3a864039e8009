"""Plain float32 references the benchmark holds the program to."""
