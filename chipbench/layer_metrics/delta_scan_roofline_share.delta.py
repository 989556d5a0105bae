"""ops / kernels: the roofline share of the gated delta rule's prefill
scan, BY SCOPE, whatever implements it: 100 x the least time the chip
could take over the measured device ms under ``mxtpu.mixer.delta.scan``,
a run of the prefill programs (an admission), averaged over their runs in
the trace.  The least time of one layer call is the larger of the bytes
it has to move (each REAL token's q, k, decay, v and step in and output
out, ``solar_open2_server.scan_bytes_per_token``, and one final state a
row written) over the HBM bytes/s and the rule's operations for each real
token (``recurrence_flops_per_token``) over the FLOP/s; real tokens a
call are the program's own count over the window's prefill dispatches
(``mxtpu_moe_routed_rows_total`` over expert-layer calls: a padded
position is routed nowhere).  The padding of a prompt to its rung and the
chunked form's extra products count for nothing, so it cannot pass 100.
A run without a trace or a program without the counts reads nothing."""
from chipbench.harness import device_scopes, resolve


def read(obs):
    table = device_scopes.of(obs)
    if not table or obs.get("peaks") is None:
        return None
    ms = device_scopes.per_run(
        device_scopes.programs(table, device_scopes.PREFILL),
        lambda p: device_scopes.scope_ms(p, "mxtpu.mixer.delta.scan"))
    server = resolve.load_module("models", "solar_open2_server")
    got = server.prefill_calls(obs)
    if not ms or got is None or not got["mxtpu_moe_layer_calls_total"]:
        return None
    shapes = server.shapes_of_run(obs["slots"])
    layers = int(shapes["num_hidden_layers"]) - len(shapes["gqa_layers"])
    tokens = got["mxtpu_moe_routed_rows_total"] \
        / got["mxtpu_moe_layer_calls_total"]
    rows = got["mxtpu_delta_state_rows_total"] \
        / got["mxtpu_delta_layer_calls_total"]
    calls = got["mxtpu_delta_layer_calls_total"] / got["dispatches"]
    state = server.state_bytes_per_slot(shapes)["ssm"] / layers
    return 100.0 * calls * server.bound_ms(
        obs, tokens * server.scan_bytes_per_token(shapes) + rows * state,
        tokens * server.recurrence_flops_per_token(shapes)) / ms
