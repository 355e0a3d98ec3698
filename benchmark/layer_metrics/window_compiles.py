"""Compile requests JAX recorded plus jit misses the program's watcher
booked, inside the window.  Expected 0."""


def read(run):
    return run.window["compile_requests"] + run.window["jit_misses"]
