"""Input generators kept with the benchmark, so that a change to the
program cannot change what it is measured on.  They are copies of the
program's generators; the program receives only the graphs and
platforms they make."""
