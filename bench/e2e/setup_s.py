"""Seconds from the start of bench/run.py until the window opens: device,
weights, model, and compiling (or loading from the cache) and running one
request of every shape."""


def read(run):
    return run["setup_s"]
