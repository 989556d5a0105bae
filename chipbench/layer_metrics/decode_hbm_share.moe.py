"""ops / kernels: the roofline of the decode step of the expert model,
whatever implements it: the bytes a decode-only round HAS to move
(``afmoe_server.decode_bytes_per_round``: every matrix applied to every
token once, the matrices of the routed experts TOUCHED, and the LIVE K,V
of the slots that decoded, each at the positions it has written) over the
round's device-busy seconds x the device's HBM bytes/s, in %.  The
experts touched in a round are the program's own count over the SAME
rounds' kind: the decode dispatches read inside the window, no prefill,
probe, ramp or drain among them (the share that ``experts_touched_share.
moe`` reads, x the experts held x the expert layers: the mean a decode
dispatch); the bytes are the median over the window's decode-only rounds;
the seconds are the median, over the decode-only rounds of the traced
seconds (``program_spans``), of the round's length less the device's idle
time in it.  A run without such a trace, or a program without the
counters, reads nothing.  Dense pages read past a slot's offset and
experts no token picked count for nothing, so the share cannot pass 100."""
import bisect

from chipbench.harness import program_spans, resolve, stats


def read(obs):
    red = program_spans.of(obs)
    busy = [length - idle for length, idle in
            (red["decode_only_rounds"] if red else ())]
    if not busy or obs.get("peaks") is None:
        return None
    builder = resolve.load_module("models", "afmoe_server")
    shapes = builder.shapes_of_run(obs["slots"])
    share = resolve.load_module(
        "layer_metrics", "experts_touched_share.moe").read(obs)
    if shapes is None or share is None:
        return None
    touched = share / 100.0 * int(shapes["num_experts"]) * (
        len(shapes["layer_types"]) - int(shapes["num_dense_layers"]))
    t0, t1 = obs["window"]
    live = [r for r in obs["requests"] if r["stamps"]]
    byts = []
    for rnd in obs["rounds"]:
        if rnd["admitted"] or not (t0 <= rnd["t0"] and rnd["t1"] <= t1):
            continue
        # a slot decoding in this round has its first token and is not done
        pos = [r["prompt_len"] + bisect.bisect_right(r["stamps"], rnd["t0"])
               for r in live if r["stamps"][0] <= rnd["t0"]
               and (r["done"] is None or r["done"] > rnd["t0"])]
        if pos:
            byts.append(builder.decode_bytes_per_round(
                shapes, len(pos), pos, touched))
    if not byts:
        return None
    return 100.0 * stats.median(byts) / (
        stats.median(busy) * obs["peaks"]["hbm_bytes_per_s"])
