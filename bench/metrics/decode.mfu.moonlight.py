"""decode.mfu.moonlight: The chip's-share FLOPs of the window's decode steps, the held experts' from the program's counter, over the window at the bf16 peak (%)."""
from bench import readers_mla_moe


def read(run):
    return readers_mla_moe.decode_mfu_pct(run)
