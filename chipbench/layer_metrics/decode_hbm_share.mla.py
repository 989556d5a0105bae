"""ops / kernels: the MEMORY roofline of the decode step of the
latent-attention model, whatever implements it: the bytes a decode-only
round HAS to move (``pangu_moe_server.decode_bytes_per_round``: every
matrix applied to every token once, the matrices of the routed experts
TOUCHED, the LIVE latent rows of the slots that decoded; the median over
the window's decode-only rounds) over the round's device-busy seconds x
the device's HBM bytes/s, in %.  The seconds are the median, over the
decode-only rounds of the traced seconds (``program_spans``), of the
round's length less the device's idle time in it; the experts touched
are the program's own count over the window's decode dispatches.  A run
without such a trace, or a program without the counters, reads nothing.
Page rows read past a slot's offset and experts no token picked count
for nothing, so the share cannot pass 100.  Its twin is
``decode_mxu_share.mla``: this step is bound by both."""
from chipbench.harness import resolve


def read(obs):
    return resolve.load_module("models", "pangu_moe_server") \
        .decode_roofline_share(obs, 0, "hbm_bytes_per_s")
