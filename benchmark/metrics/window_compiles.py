"""Compile and warm start: programs the process acquired inside the
measured window (backend-compile events, persistent-cache hits included)."""


def read(run):
    return run.window_compiles
