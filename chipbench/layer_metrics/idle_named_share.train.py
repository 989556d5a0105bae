"""device: share of device 0's idle time in the traced window whose gap
has its midpoint in a LEAF ``mxtpu.*`` span: idle time that the program's
own spans attribute to a phase, not merely to a step or a round."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.idle_named_share(obs)
