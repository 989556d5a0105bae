#!/usr/bin/env python3
"""The limits of ``correct`` in a routed-expert cell
(``trinity_large_ep8.decode_closed``, or ``--cell
pangu_ultra_moe_ep16.longgen_closed``), read ON THE CHIP through the
harness's own comparison.

    chiprun -- python tools/afmoe_chip_check.py --seeds 2147491201,...

Builds the cell's server as a run does and, for each seed (the weights
are re-drawn into the same net, one parameter at a time: two models do
not fit a chip), calls the serving driver's own ``_probe`` (the probe
request served through ``Server``, its greedy tokens held to the
builder's ``full_forward_logits``: the plain reference at the STATED
precision, its sum over the experts the served programs picked, the picks
held to the reference's own; ``chipbench/models/afmoe_server.py`` has the rule).
Then, on the SAME served tokens, the readings the limits were set from,
one JSON line each: the reference picking for itself (``own``) and given
the program's picks (``given``), at the stated precision and with the
``float8`` control, each with the worst served token's regret and, for
``given``, where the picks differ and by what margin; then every further
control the builder's reference knows (a ``PRECISIONS`` key beyond those
three: Pangu's ``softmax_bfloat16``), and for a latent-attention model a
reference whose shared rotated key is zero (the ``q_r . k_r`` term left
out), each as the cell would run it.  For a model whose reference also
compares the served matrix states (``--cell
solar_open2_ep8.bulkgen_closed``) every control runs through that whole
comparison, and the reference's line gives the states' readings.  PERF.md
section 6 has the readings.  ``--rehearse`` runs the config's toy shapes
on any backend.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "trinity_large_ep8.decode_closed"


def _regrets(logits, req):
    import numpy as np
    out = []
    for i, tok in enumerate(req.generated):
        row = logits[req.prompt_len - 1 + i]
        out.append(float((row.max() - row[tok]) / np.abs(row).max()))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true",
                    help="the config's toy rehearsal shapes, any backend")
    ap.add_argument("--seeds", default="2147491201")
    ap.add_argument("--cell", default=CELL)
    args = ap.parse_args()

    from tools import jax_cache
    jax_cache.place()
    import jax
    import numpy as np
    from chipbench.drivers import serve_loop
    from chipbench.harness import resolve, runtime

    workload, config, traffic = resolve.cell(resolve.load_benchmark(),
                                             args.cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    run = runtime.Run(
        types.SimpleNamespace(seed=seeds[0], seconds=0.0, trace=0,
                              rehearse=args.rehearse, sweep=None),
        workload, config, traffic, None, 0.0)
    run.devices = jax.devices()[:1]
    builder = runtime.builder_for(run)
    net, srv, ctx = builder.build_server(
        run.shapes, seeds[0], run.devices[0], int(run.traffic["max_queue"]))
    loop = serve_loop.Loop(srv, run.spans)
    dtype = run.shapes["serving"]["weight_dtype"]
    weights = None
    for n, seed in enumerate(seeds):
        if n:
            del weights         # the old arrays must be free to go
            for p, value in zip(net.collect_params().values(),
                                builder.draw_weights(net, seed,
                                                     run.devices[0], dtype)):
                p.data(ctx)._set_data(value)
            srv.statistics_listener.arm()   # this probe's picks
        run.seed = seed
        run.checks = runtime.Checks()
        seen = {}

        def capture(net_, toks, ctx_):
            seen["tokens"] = np.asarray(toks)
            return builder.full_forward_logits(net_, toks, ctx_)

        probe = serve_loop._probe(
            run, net, srv, ctx,
            types.SimpleNamespace(full_forward_logits=capture), loop)
        print(json.dumps(dict(
            probe, seed=seed, rule="as the cell runs it",
            gap_share=run.traffic["probe"]["gap_share"],
            held=not run.checks.failed)), flush=True)
        req = loop.requests[-1]["req"]
        tokens = seen["tokens"]
        # the control, by the rule as the cell runs it: it has to fail
        control = builder.full_forward_logits(net, tokens, ctx,
                                              precision="float8")
        worst = max(_regrets(control, req))
        print(json.dumps({
            "seed": seed, "rule": "the float8 control, as the cell would "
            "run it", "probe_worst_regret_share": worst,
            "gap_share": run.traffic["probe"]["gap_share"],
            "held": worst <= float(run.traffic["probe"]["gap_share"])}),
            flush=True)
        weights, cfg, held = builder._weights_and_config(net, ctx)
        chosen = builder.served_picks(net, len(tokens))
        limit = float(run.traffic["probe"]["gap_share"])
        controls = [name for name in builder.PRECISIONS
                    if name not in ("float32", "stated", "float8")]
        if any(k.endswith("attn_dkv_weight") for k in weights):
            controls.append("no_k_r_term")
        for name in controls:
            if hasattr(builder, "served_states"):
                # a model whose reference also holds the served matrix
                # states to its own: the control as the cell would run it
                worst = max(_regrets(builder.full_forward_logits(
                    net, tokens, ctx, precision=name), req))
                print(json.dumps({
                    "seed": seed, "rule": f"the {name} control, as the cell "
                    "would run it", "probe_worst_regret_share": worst,
                    "gap_share": limit, "held": worst <= limit}), flush=True)
                continue
            w, precision = weights, name
            if name == "no_k_r_term":
                # latent attention without its shared rotated key: the
                # rows of W_dkv that make k_r are zero, so q_r . k_r adds
                # nothing
                rope = int(run.shapes["qk_rope_head_dim"])
                w, precision = {
                    k: (v.at[-rope:].set(0) if k.endswith("attn_dkv_weight")
                        else v) for k, v in weights.items()}, "stated"
            worst = max(_regrets(builder.forward_logits(
                w, tokens, cfg, precision, held, selections=chosen), req))
            del w       # nothing may hold the old weights at a re-draw
            print(json.dumps({
                "seed": seed, "rule": f"the {name} control, given the "
                "served picks", "probe_worst_regret_share": worst,
                "gap_share": limit, "held": worst <= limit}), flush=True)
        for precision in ("stated", "float8"):
            for name, given in (("own", None), ("given", chosen)):
                routing = {}
                logits = builder.forward_logits(
                    weights, tokens, cfg, precision, held, selections=given,
                    routing=routing)
                differ = (np.sort(chosen, -1)
                          != np.sort(routing["picked"], -1)).any(-1)
                margins = np.sort(routing["margin"][differ])
                served = slice(req.prompt_len - 1, None)
                print(json.dumps({
                    "seed": seed, "precision": precision, "picks": name,
                    "worst_regret_share": max(_regrets(logits, req)),
                    "decisions": int(differ.size),
                    "picks_differ": int(differ.sum()),
                    "picks_differ_on_served_rows": int(
                        differ[served].sum()),
                    "margins_where_they_differ": [
                        float(m) for m in np.concatenate(
                            [margins[:3], margins[-5:]])],
                    "margin_quantiles_all_rows": [
                        float(q) for q in np.quantile(
                            routing["margin"], [0.001, 0.01, 0.1, 0.5])],
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
