"""ops / kernels: an END-TO-END utilization of the whole step, not a
kernel's roofline share: this run's output tokens/s x the operations a
generated token needs in the products THIS CHIP applies to it
(``pangu_moe_server.flops_per_token``: every matrix outside the routed
experts, the expected ``num_experts_per_tok`` x held / routed experts an
expert layer, and the absorbed attention at the mean number of positions
a decoding row of the window had written, from the program's own counts)
over the device_kind's bf16 peak.  Prompt tokens' operations are left
out.  A program without the counts gives None."""
from chipbench.harness import readers, resolve


def read(obs):
    if obs.get("peaks") is None or "requests" not in obs:
        return None
    builder = resolve.load_module("models", "pangu_moe_server")
    live = builder.mean_live_positions(obs)
    if live is None:
        return None
    t0, t1 = obs["window"]
    rate = readers.tokens_in_window(obs) / (t1 - t0)
    return 100.0 * rate * builder.flops_per_token(
        builder.shapes_of_run(obs["slots"]), live) / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
