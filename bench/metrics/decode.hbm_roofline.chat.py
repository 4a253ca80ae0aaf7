"""decode.hbm_roofline.chat: Parameter and occupied K/V bytes per step at peak HBM bandwidth over the step's device time (%)."""
from bench import readers


def read(run):
    return readers.decode_hbm_roofline_pct(run)
