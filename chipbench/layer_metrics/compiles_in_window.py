"""Compile requests (jax.monitoring) between the window's start and its end.
A count, expected to read 0: a compile inside the window is time in which no
solve completes."""


def read(run):
    return run["compiles_in_window"]
