"""moe.tokens_per_held_expert.moonlight: Choices routed to held experts per step, expert layer and held expert, from the program's counters (tokens)."""
from bench import readers_mla_moe


def read(run):
    return readers_mla_moe.tokens_per_held_expert(run)
