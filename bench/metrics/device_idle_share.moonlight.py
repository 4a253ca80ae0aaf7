"""device_idle_share.moonlight: 1 - union of device-operation intervals over the traced window (%)."""
from bench import readers


def read(run):
    return readers.idle_share_pct(run)
