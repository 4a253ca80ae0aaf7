"""wah.xla_ms_per_call: Device time of every other device operation per ask in the traced window (ms)."""
from bench import readers


def read(run):
    return readers.kernel_ms_per_call(run, pallas=False)
