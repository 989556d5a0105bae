"""engine + tiers: set-up seconds spent in the first call of each
program, where it is traced and compiled or loaded from a cache tier."""
from chipbench.harness import readers


def read(obs):
    return readers.first_call_seconds(obs)
