"""ops / kernels: tokens an expert held here is given in one call of an
expert layer in a DECODE round of the latent-attention model: the
program's own counts, held assignments over expert-layer calls x the
experts held, summed over the decode dispatches read inside the window
(``pangu_moe_server.decode_calls``; the reduction of
``expert_tokens_per_round.moe``, whose reader is bound to its own
builder).  A program without the counts gives None."""
from chipbench.harness import resolve


def read(obs):
    return resolve.load_module("models", "pangu_moe_server") \
        .per_held_expert_call(obs, "mxtpu_moe_assignments_held_total")
