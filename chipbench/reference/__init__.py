"""Plain references the benchmark compares the program against.  They import
nothing of the program under test."""
