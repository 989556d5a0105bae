"""ops / kernels: the roofline share of the absorbed latent attention of
a decode run, BY SCOPE, whatever implements it: 100 x the least time the
chip could take over the measured device ms under
``mxtpu.mixer.mla.attend`` in the decode program.  The bound of one layer
call is the larger of the LIVE positions' latent rows over the HBM
bytes/s and ``pangu_moe_server.attention_flops_per_position`` x the live
positions over the FLOP/s (they are within 1% of one another: absorbed
MLA sits at the chip's ridge), times the layer calls a run; live
positions a layer call are the program's own count over the window's
decode dispatches (``pangu_moe_server.decode_calls``).  Positions past a
slot's offset count for nothing, so it cannot pass 100."""
from chipbench.harness import device_scopes, resolve


def read(obs):
    ms = device_scopes.decode_scope_ms(obs, "mxtpu.mixer.mla.attend")
    if not ms:
        return None
    server = resolve.load_module("models", "pangu_moe_server")
    got = server.decode_calls(obs)
    if got is None or not got["mxtpu_mla_layer_calls_total"]:
        return None
    shapes = server.shapes_of_run(obs["slots"])
    live = got["mxtpu_mla_live_positions_total"] \
        / got["mxtpu_mla_layer_calls_total"]
    # one position's row in one layer's page, as the pool stores it
    (_slots, prompt), = shapes["serving"]["buckets"]
    row_bytes = server.state_bytes_per_slot(shapes)["kv_latent"] / (
        int(shapes["num_hidden_layers"])
        * (prompt + int(shapes["serving"]["max_new_tokens"])))
    bound_s = max(live * row_bytes / obs["peaks"]["hbm_bytes_per_s"],
                  live * server.attention_flops_per_position(shapes)
                  / obs["peaks"]["bf16_flops_per_s"])
    calls_a_run = got["mxtpu_mla_layer_calls_total"] / got["dispatches"]
    return 100.0 * calls_a_run * bound_s * 1e3 / ms
