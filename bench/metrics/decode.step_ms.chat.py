"""decode.step_ms.chat: Device time per execution of the jitted decode-step program (ms)."""
from bench import readers


def read(run):
    return readers.decode_step_ms(run)
