"""decode.hbm_roofline.moonlight: Non-expert weight, touched-expert weight (program counter) and occupied latent-cache bytes per step at peak HBM bandwidth over the step's device time (%)."""
from bench import readers_mla_moe


def read(run):
    return readers_mla_moe.decode_hbm_roofline_pct(run)
