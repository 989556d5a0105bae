"""The yardstick: everything here is the benchmark's own and takes from
the program only the system under test, its counters and its events."""
