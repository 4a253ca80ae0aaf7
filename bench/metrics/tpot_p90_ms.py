"""tpot_p90_ms: 90th percentile over the same requests of (last token - first token) / (tokens - 1) (host clock)."""
from bench import readers


def read(run):
    return readers.tpot_p_ms(run, 90)
