"""device: share of the traced ~3 s mid-window in which no operation ran
on device 0 (profiler trace; union of the ``XLA Ops`` intervals)."""
from chipbench.harness import readers


def read(obs):
    return readers.idle_share(obs)
