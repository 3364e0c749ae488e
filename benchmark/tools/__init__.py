"""Tools run by hand, never by a run of the benchmark."""
