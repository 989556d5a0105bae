"""ops / kernels: the roofline share of the gated delta rule's one-token
update in a decode run, BY SCOPE, whatever implements it: 100 x the least
time the chip could take over the measured device ms under
``mxtpu.mixer.delta.update`` in the decode program.  The least time is
the larger of the matrix states of the slots that held a request, read
and written (``solar_open2_server.active_state_rows``: the program's
``mxtpu_delta_state_rows_total`` a dispatch times the share of its slots
with a request, x 2 x heads x dk x dv x 4 B), over the HBM bytes/s, and
the rule's operations for those rows
(``solar_open2_server.recurrence_flops_per_token``) over the FLOP/s.
Idle slots' states count for nothing, so it cannot pass 100.  A run
without a trace or a program without the counts reads nothing."""
from chipbench.harness import device_scopes, resolve


def read(obs):
    ms = device_scopes.decode_scope_ms(obs, "mxtpu.mixer.delta.update")
    if not ms or obs.get("peaks") is None:
        return None
    server = resolve.load_module("models", "solar_open2_server")
    rows = server.active_state_rows(obs)
    if rows is None:
        return None
    shapes = server.shapes_of_run(obs["slots"])
    layers = int(shapes["num_hidden_layers"]) - len(shapes["gqa_layers"])
    state = server.state_bytes_per_slot(shapes)["ssm"] / layers
    return 100.0 * server.bound_ms(
        obs, rows * 2 * state,
        rows * server.recurrence_flops_per_token(shapes)) / ms
