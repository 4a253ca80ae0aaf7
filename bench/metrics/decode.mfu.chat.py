"""decode.mfu.chat: Model FLOPs of the window's decode steps over the window at the bf16 peak (%)."""
from bench import readers


def read(run):
    return readers.decode_mfu_pct(run)
