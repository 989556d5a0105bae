"""ops / kernels: tokens an expert held here is given in one call of an
expert layer in a DECODE round of the linear-attention model: the
program's own counts, held assignments over expert-layer calls x the
experts held, summed over the decode dispatches read inside the window
(``solar_open2_server.decode_calls``).  A program without the counts
gives None."""
from chipbench.harness import resolve


def read(obs):
    return resolve.load_module("models", "solar_open2_server") \
        .per_held_expert_call(obs, "mxtpu_moe_assignments_held_total")
