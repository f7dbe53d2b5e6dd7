"""The benchmark's yardstick: everything here is the benchmark's own copy, so
a later PR that changes the program cannot move it."""
