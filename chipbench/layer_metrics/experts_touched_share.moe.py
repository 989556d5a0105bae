"""ops / kernels: the share of the experts held here that receive at
least one token in a call of an expert layer in a DECODE round, in %: the
program's own counts ``mxtpu_moe_experts_touched_total`` over
``mxtpu_moe_layer_calls_total`` x the experts held, summed over the
decode dispatches read inside the window (``afmoe_server.decode_calls``).
What is not touched need not be read: ``decode_hbm_share.moe`` counts
the touched experts' bytes only.  A program without the counts gives
None."""
from chipbench.harness import resolve


def read(obs):
    share = resolve.load_module("models", "afmoe_server") \
        .per_held_expert_call(obs, "mxtpu_moe_experts_touched_total")
    return None if share is None else 100.0 * share
