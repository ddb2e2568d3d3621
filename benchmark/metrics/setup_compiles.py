"""Compile and warm start: programs compiled during set-up that the
persistent compile cache did not hold (backend compiles minus cache hits)."""


def read(run):
    return run.setup_compiles
