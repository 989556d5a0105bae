"""ops / kernels: the roofline of the decode step, whatever implements
it: the bytes a decode-only round HAS to move (``sambay_server.
decode_bytes_per_round``: every weight once, and the LIVE state of the
slots that decoded, each at the positions it has written) over the
round's device-busy seconds x the device's HBM bytes/s, in %.  The bytes
are the median over the window's decode-only rounds; the seconds are the
median, over the decode-only rounds of the traced seconds
(``program_spans``), of the round's length less the device's idle time
in it.  A run without such a trace reads nothing: a host clock is no
source for a device's roofline.  Dense pages the program reads past a
slot's offset are its waste and count for nothing, so the share cannot
pass 100."""
import bisect

from chipbench.harness import program_spans, resolve, stats


def read(obs):
    red = program_spans.of(obs)
    busy = [length - idle for length, idle in
            (red["decode_only_rounds"] if red else ())]
    if not busy or obs.get("peaks") is None:
        return None
    builder = resolve.load_module("models", "sambay_server")
    shapes = builder.shapes_of_run(obs["slots"])
    if shapes is None:
        return None
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
            byts.append(builder.decode_bytes_per_round(shapes, len(pos), pos))
    if not byts:
        return None
    return 100.0 * stats.median(byts) / (
        stats.median(busy) * obs["peaks"]["hbm_bytes_per_s"])
