"""moe.experts_hbm_roofline.moonlight: Touched-expert weight bytes of a mean expert layer-step (program counter) at peak HBM bandwidth over the mean device time of one run of the held-expert Pallas kernel (%)."""
from bench import readers_mla_moe


def read(run):
    return readers_mla_moe.experts_hbm_roofline_pct(run)
