"""ops / kernels: the COMPUTE roofline of the decode step of the
latent-attention model, whatever implements it: the operations a
decode-only round HAS to do (``pangu_moe_server.decode_flops_per_round``:
two a matrix weight for each slot that decodes, two a routed expert's
weight for each assignment on a held expert, and the absorbed attention,
278,528 a LIVE position a layer a row at the published widths; the median
over the window's decode-only rounds) over the round's device-busy
seconds (as ``decode_hbm_share.mla`` takes them) x the device's bf16
FLOP/s, in %.  A run without such a trace, or a program without the
counters, reads nothing.  Idle slots' rows, positions past a slot's
offset and a grouped product's padding count for nothing, so the share
cannot pass 100."""
from chipbench.harness import resolve


def read(obs):
    return resolve.load_module("models", "pangu_moe_server") \
        .decode_roofline_share(obs, 1, "bf16_flops_per_s")
