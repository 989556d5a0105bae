"""serving: median wall time of the ``Server.step()`` rounds inside the
window that admitted nothing (one decode dispatch per busy bucket, each
closed by a host read of its tokens)."""
from chipbench.harness import readers


def read(obs):
    return readers.decode_round_ms(obs)
