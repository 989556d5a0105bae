"""ops / kernels: an END-TO-END utilization of the whole step, not a
kernel's roofline share: this run's output tokens/s x the operations a
generated token needs in the matrix products THIS CHIP applies to it
(``afmoe_server.flops_per_token``: every matrix outside the routed
experts, and the expected ``num_experts_per_tok`` x held / routed experts
an expert layer) over the device_kind's bf16 peak.  Prompt tokens'
operations and attention's own products are left out."""
from chipbench.harness import readers, resolve


def read(obs):
    if obs.get("peaks") is None or "requests" not in obs:
        return None
    builder = resolve.load_module("models", "afmoe_server")
    shapes = builder.shapes_of_run(obs["slots"])
    if shapes is None:
        return None
    t0, t1 = obs["window"]
    rate = readers.tokens_in_window(obs) / (t1 - t0)
    return 100.0 * rate * builder.flops_per_token(shapes) / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
