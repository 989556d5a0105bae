#!/usr/bin/env python
"""mxmem: render the memory & communication observatory's report.

The observatory (``mxnet_tpu.telemetry.memory``) harvests per-program
memory/FLOPs accounting from every compiled executable the engine's
tiered AOT seam produces, plus a live-buffer census, per-param HBM
attribution, and analytic collective traffic.  This tool renders that
data three ways:

    python tools/mxmem.py smoke              # run a tiny in-process
                                             # workload, then report
    python tools/mxmem.py render report.json # render a saved report
                                             # (memory.dump_report)
    # live process: from tools.mxmem import render_report
    #               print(render_report(telemetry.memory.report(
    #                   params=net.collect_params())))

Sections: top-N programs by peak bytes (``MXTPU_MEM_REPORT_TOP_N``),
the per-param HBM table, per-collective traffic, and the live census
against device capacity.  See docs/observability.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
# NOTE: no JAX_PLATFORMS mutation at import time — render_report is
# documented for import into LIVE training processes, and a module-
# level setdefault would silently pin such a process to CPU.  The CLI
# entry point (main) pins it instead.


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


def render_report(rep: dict) -> str:
    """Text rendering of a ``telemetry.memory.report()`` dict."""
    lines = []
    progs = rep.get("programs", [])
    lines.append(f"programs by peak footprint "
                 f"(showing {len(progs)} of {rep.get('n_programs', 0)})")
    lines.append(f"{'PROGRAM':44} {'PEAK':>9} {'TEMP':>9} {'ARGS':>9} "
                 f"{'DONATED':>9} {'GFLOP':>7} {'WIRE':>9} SRC")
    for r in progs:
        flops = r.get("flops")
        gflop = f"{flops / 1e9:.3f}" if flops is not None else "-"
        lines.append(
            f"{str(r['name'])[:44]:44} "
            f"{_fmt_bytes(r.get('peak_bytes')):>9} "
            f"{_fmt_bytes(r.get('temp_bytes')):>9} "
            f"{_fmt_bytes(r.get('argument_bytes')):>9} "
            f"{_fmt_bytes(r.get('donation_saved_bytes')):>9} "
            f"{gflop:>7} "
            f"{_fmt_bytes(r.get('collective_wire_bytes')):>9} "
            f"{'analytic' if r.get('analytic') else 'xla'}"
            f"/{r.get('source', '?')}")
    coll = rep.get("collectives") or {}
    lines.append("")
    if coll:
        lines.append("collective traffic (analytic, per device per "
                     "step)")
        lines.append(f"{'KIND':22} {'COUNT':>6} {'PAYLOAD':>10} "
                     f"{'ON-WIRE':>10}")
        for kind, row in sorted(coll.items()):
            lines.append(f"{kind:22} {row['count']:>6} "
                         f"{_fmt_bytes(row['payload_bytes']):>10} "
                         f"{_fmt_bytes(row['wire_bytes']):>10}")
    else:
        lines.append("collective traffic: none harvested (single-"
                     "device programs, or nothing compiled yet)")
    pc = rep.get("param_census")
    if pc:
        lines.append("")
        lines.append(f"param HBM attribution ({pc['count']} params, "
                     f"{_fmt_bytes(pc['total_bytes'])} total)")
        lines.append(f"{'PARAM':44} {'BYTES':>10} {'SHARDING':20}")
        for row in pc["params"]:
            shard = "replicated" if row["replicated"] else \
                str(row["sharding"])
            lines.append(f"{str(row['name'])[:44]:44} "
                         f"{_fmt_bytes(row['nbytes']):>10} "
                         f"{shard[:20]:20}")
    opt = rep.get("opt_states") or {}
    for tname, tree in sorted(opt.items()):
        lines.append("")
        lines.append(
            f"optimizer state [{tname}] "
            f"(zero_stage={tree.get('zero_stage', 0)}, "
            f"dp={tree.get('dp_size', 1)}): "
            f"{_fmt_bytes(tree.get('total_bytes'))} global, "
            f"{_fmt_bytes(tree.get('per_device_bytes'))}/device "
            f"({_fmt_bytes(tree.get('replicated_bytes'))} replicated "
            f"+ {_fmt_bytes(tree.get('sharded_bytes_per_device'))} "
            "sharded shard)")
        lines.append(f"{'LEAF':44} {'GLOBAL':>10} {'PER-DEV':>10} "
                     f"{'SHARDING':20}")
        for row in tree.get("leaves", []):
            shard = "replicated" if row["replicated"] else \
                str(row["sharding"])
            lines.append(f"{str(row['name'])[:44]:44} "
                         f"{_fmt_bytes(row['nbytes']):>10} "
                         f"{_fmt_bytes(row['bytes_per_device']):>10} "
                         f"{shard[:20]:20}")
    live = rep.get("live") or {}
    cap = rep.get("device_capacity_bytes")
    lines.append("")
    lines.append(
        f"live buffers: {live.get('count', 0)} arrays, "
        f"{_fmt_bytes(live.get('total_bytes', 0))} "
        + (f"of {_fmt_bytes(cap)} capacity "
           f"({100.0 * live.get('total_bytes', 0) / cap:.1f}%)"
           if cap else "(device capacity unknown on this backend)"))
    for dev, b in sorted((live.get("by_device") or {}).items()):
        lines.append(f"  {dev:30} {_fmt_bytes(b):>10}")
    return "\n".join(lines)


def cmd_render(args) -> int:
    with open(args.report) as f:
        rep = json.load(f)
    # anything without the report's schema is not a memory report
    if "programs" not in rep:
        print(f"mxmem: {args.report} does not look like a memory "
              "report (no 'programs' key)", file=sys.stderr)
        return 2
    if args.fmt == "json":
        print(json.dumps(rep, indent=2, default=str))
    else:
        print(render_report(rep))
    return 0


def cmd_smoke(args) -> int:
    """Tiny in-process workload so the CLI demonstrates the live path
    end-to-end: a compiled gluon step (donated), and — when the
    backend exposes more than one device — a fused SPMD step whose
    gradient all-reduce shows up in the collective table."""
    # an 8-way virtual host mesh (same as the test harness) so the
    # SPMD leg has real collectives to count; must precede jax import
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags and \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel, telemetry
    from mxnet_tpu.gluon import nn

    def build():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(128, activation="relu", in_units=64),
                    nn.Dense(16, in_units=128))
        net.initialize(mx.init.Xavier())
        return net

    net = build()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore=None)
    cs = tr.compile_step(net, gluon.loss.L2Loss())
    x = nd.array(np.random.rand(32, 64).astype("f4"))
    y = nd.array(np.random.rand(32, 16).astype("f4"))
    for _ in range(2):
        loss = cs.step(x, y, 32)
    loss.wait_to_read()

    import jax
    if len(jax.devices()) > 1:
        net2 = build()
        mesh = parallel.make_mesh({"dp": len(jax.devices())})
        dpt = parallel.DataParallelTrainer(
            net2, gluon.loss.L2Loss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh, fuse_step=True)
        dpt.step(x, y).wait_to_read()
    mx.nd.waitall()

    rep = telemetry.memory.report(params=net.collect_params())
    if args.out:
        telemetry.memory.dump_report(args.out,
                                     params=net.collect_params())
        print(f"report written to {args.out}", file=sys.stderr)
    if args.fmt == "json":
        print(json.dumps(rep, indent=2, default=str))
    else:
        print(render_report(rep))
    return 0


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="mxmem", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--format", choices=["text", "json"],
                    default="text", dest="fmt")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("render", help="render a saved memory report")
    p.add_argument("report", help="JSON from memory.dump_report()")
    p = sub.add_parser("smoke",
                       help="run a tiny workload, then report")
    p.add_argument("--out", default="",
                   help="also dump the report JSON here")
    args = ap.parse_args(argv)
    return {"render": cmd_render, "smoke": cmd_smoke}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
