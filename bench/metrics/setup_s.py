"""Seconds from process start to the first request: importing, making the
weights, building the engine, loading or compiling every program the
window runs, and warming them up."""


def read(run):
    return run["setup_s"]
