"""calls_per_s: Completed calls over the window, all clients together; the window ends when the last call started in it completes (host clock)."""
from bench import readers


def read(run):
    return readers.calls_per_s(run)
