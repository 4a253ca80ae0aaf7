"""ttft_p90_ms: 90th percentile, over every request due in the window, of due time to first token (host clock)."""
from bench import readers


def read(run):
    return readers.ttft_p_ms(run, 90)
