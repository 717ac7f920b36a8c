"""Bit rate at the client: bytes of the fragments that arrived inside the
window, over the window."""


def read(run):
    return run["bytes_in_window"] * 8 / 1e3 / run["seconds"]
