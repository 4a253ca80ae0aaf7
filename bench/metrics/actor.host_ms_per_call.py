"""actor.host_ms_per_call: Per ask, the part of the benchmark's bench.ask span that no device operation covers (ms)."""
from bench import readers


def read(run):
    return readers.host_ms_per_call(run)
