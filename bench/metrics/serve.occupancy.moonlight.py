"""serve.occupancy.moonlight: Batch slots filled over steps x max_batch in the window, from the engine's counters (%)."""
from bench import readers


def read(run):
    return readers.occupancy_pct(run)
