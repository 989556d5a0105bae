"""ops / kernels: tokens an expert held here is given in one call of an
expert layer in a DECODE round: the program's own counts (the model
counts in its programs, the counts ride out behind each dispatch's tokens,
``serving.Server`` adds them to ``mxtpu_moe_assignments_held_total`` and
``mxtpu_moe_layer_calls_total`` and hands them, call by call, to the
run's listener): held assignments over expert-layer calls x the experts
held, summed over the decode dispatches read inside the window
(``afmoe_server.decode_calls``: prefills, the lone-row probe, the ramp
and the drain are outside).  A program without the counts gives None."""
from chipbench.harness import resolve


def read(obs):
    return resolve.load_module("models", "afmoe_server") \
        .per_held_expert_call(obs, "mxtpu_moe_assignments_held_total")
