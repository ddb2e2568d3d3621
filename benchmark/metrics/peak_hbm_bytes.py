"""The device: `peak_bytes_in_use` of the fullest chip after the window."""


def read(run):
    return run.peak_bytes
