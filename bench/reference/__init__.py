"""Plain references the benchmark's ``correct`` rests on.  They import
nothing of the program and take nothing it made."""
