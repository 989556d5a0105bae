#!/usr/bin/env python
"""mxwire: the jaxpr-level wire-leg auditor, standalone.

The wire pass (``analysis.wire_passes``; docs/static_analysis.md "The
wire auditor") walks the closed jaxpr of every compiled fused-step
variant the trainers and the serving plane register, builds a wire-leg
inventory (every psum / reduce-scatter / all-gather / all-to-all /
ppermute classified by leg kind — dp grad sync, ZeRO scatter/gather,
tp activation, gated stats row), and checks the MXL8xx wire contracts:
declared per-leg precision (MXL801), the ZeRO-2 reduce-scatter shape
(MXL802), sampling gates on observability rows (MXL803), and static
bytes-on-wire vs the memory observatory's runtime accounting (MXL804).

The registry is process-local, so this tool runs a small demo workload
on the 8-virtual-device CPU mesh first, then audits what it compiled:

    python tools/mxwire.py show --model mlp
        # per-variant wire-leg table: op, leg kind, axes, dtype,
        # payload + on-wire bytes, gate/obs flags; static total vs the
        # observatory's measured bytes and the drift ratio

    python tools/mxwire.py show --model mlp --zero-stage 2
        # the explicit ZeRO-2 legs (reduce-scatter + all-gather)

    python tools/mxwire.py lint --model mlp --compress int8
        # the MXL8xx audit over the compressed exchange — exit 1 on
        # error-severity findings (``--fail-on warning`` tightens)

    python tools/mxwire.py lint --model mlp --precision dp_grad=int8
        # declare a leg precision and let MXL801 check the jaxpr
        # against it (a dense fp32 grad leg under an int8 declaration
        # is the silent-widening class the rule exists for)

``--model`` picks a shipped demo (``mlp`` | ``llama_tiny``); the
workload is 3 fused steps.
"""
from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def _parse_precision(pairs):
    """``["dp_grad=int8", ...]`` -> validated precision dict."""
    from mxnet_tpu.parallel import planner
    prec = {}
    for pair in pairs or ():
        leg, _, dt = pair.partition("=")
        if not dt:
            print(f"mxwire: --precision wants leg=dtype, got {pair!r}",
                  file=sys.stderr)
            raise SystemExit(1)
        prec[leg.strip()] = dt.strip()
    if prec:
        # validate eagerly via the plan constructor's own rules
        planner.ShardingPlan({"dp": 1}, precision=prec)
    return prec or None


def _run_workload(args):
    """Build + step a fused demo trainer so the wire registry holds a
    real compiled variant, then return the trainer (kept alive so the
    registered fn stays traceable)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    if args.zero_stage:
        os.environ["MXTPU_ZERO_STAGE"] = str(args.zero_stage)
    np.random.seed(0)
    mx.random.seed(0)
    prec = _parse_precision(args.precision)
    kw = {}
    if args.compress:
        kw["compression"] = {"type": args.compress}
    if args.model == "mlp":
        from mxnet_tpu.gluon import nn
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(128, activation="relu", in_units=64),
                    nn.Dense(10, in_units=128))
        net.initialize(mx.init.Xavier())
        if prec:
            kw["plan"] = parallel.ShardingPlan({"dp": 8},
                                               precision=prec)
            mesh = None
        else:
            mesh = parallel.make_mesh({"dp": 8})
        sce = SoftmaxCrossEntropyLoss()
        dpt = parallel.DataParallelTrainer(
            net, sce, "adam", {"learning_rate": 1e-3}, mesh=mesh,
            fuse_step=True, **kw)
        X = np.random.RandomState(0).randn(32, 64).astype("f4")
        Y = np.random.RandomState(1).randint(0, 10, 32).astype("f4")
    elif args.model == "llama_tiny":
        from mxnet_tpu.models import LlamaForCausalLM, llama_tiny
        net = LlamaForCausalLM(llama_tiny(vocab_size=64))
        net.initialize(mx.init.Xavier())
        mesh = parallel.make_mesh({"dp": 8})
        if prec:
            kw["plan"] = parallel.ShardingPlan({"dp": 8},
                                               precision=prec)
            mesh = None
        sce = SoftmaxCrossEntropyLoss()

        def lm_loss(logits, toks):
            v = logits.shape[-1]
            return sce(logits[:, :-1].reshape((-1, v)),
                       toks[:, 1:].reshape((-1,))).mean()
        dpt = parallel.DataParallelTrainer(
            net, lm_loss, "adam", {"learning_rate": 1e-3}, mesh=mesh,
            fuse_step=True, **kw)
        X = np.random.RandomState(0).randint(0, 64, (8, 16)) \
            .astype("f4")
        Y = X
    else:
        print(f"mxwire: unknown --model {args.model!r} "
              "(mlp | llama_tiny)", file=sys.stderr)
        raise SystemExit(1)
    for _ in range(3):
        loss = dpt.step(nd.array(X), nd.array(Y))
    loss.wait_to_read()
    return dpt


def cmd_show(args) -> int:
    from mxnet_tpu.analysis import wire_passes
    _dpt = _run_workload(args)
    rep = wire_passes.wire_report()
    if not rep:
        print("mxwire: no step variants registered (is "
              "MXTPU_WIRE_AUDIT=0 set?)", file=sys.stderr)
        return 1
    for name, v in sorted(rep.items()):
        bits = [f"kind={v['kind']}", f"zero_stage={v['zero_stage']}"]
        if v["compressed"]:
            bits.append("compressed")
        if v["sampled"]:
            bits.append("sampled")
        if v["derived"]:
            bits.append("derived-dense-model")
        print(f"{name}: {', '.join(bits)}")
        if v["trace_error"]:
            print(f"  trace unavailable: {v['trace_error']}")
            continue
        w = max((len(leg["kind"]) for leg in v["legs"]), default=4)
        for leg in v["legs"]:
            flags = "".join((
                "g" if leg["gated"] else "-",
                "o" if leg["obs_only"] else "-",
                "i" if leg["implicit"] else "-"))
            print(f"  {leg['kind'].ljust(w)}  "
                  f"{leg['op']:<18} {'x'.join(leg['axes']):<6} "
                  f"{leg['dtype']:<9} payload {leg['payload_bytes']:>9}"
                  f"  wire {leg['wire_bytes']:>9}  [{flags}]")
        meas = v["measured_wire_bytes"]
        drift = ("" if v["drift"] is None
                 else f"  drift {v['drift'] * 100:.2f}%")
        print(f"  static {v['static_wire_bytes']} B"
              + (f"  measured {meas} B{drift}" if meas is not None
                 else "  (no observatory program to reconcile)"))
    return 0


def cmd_lint(args) -> int:
    from mxnet_tpu import analysis
    _dpt = _run_workload(args)
    findings = analysis.analyze_wire()
    for f in findings:
        print(f.format())
    if not findings:
        print("mxwire: wire contracts clean (MXL801-804)")
    bad = [f for f in findings
           if f.severity == "error"
           or (args.fail_on == "warning" and f.severity == "warning")]
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mxwire", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    def _common(p):
        p.add_argument("--model", default="mlp",
                       help="mlp | llama_tiny (demo workload)")
        p.add_argument("--zero-stage", type=int, default=0,
                       choices=(0, 1, 2, 3))
        p.add_argument("--compress", default="",
                       help="int8 | 2bit (gradient compression)")
        p.add_argument("--precision", action="append", default=[],
                       metavar="LEG=DTYPE",
                       help="declare a plan wire precision, e.g. "
                       "dp_grad=int8 (repeatable); MXL801 checks the "
                       "jaxpr against it")
    p_show = sub.add_parser("show", help="per-variant wire-leg table")
    _common(p_show)
    p_lint = sub.add_parser("lint",
                            help="MXL8xx wire audit, standalone")
    _common(p_lint)
    p_lint.add_argument("--fail-on", choices=["error", "warning"],
                        default="error")
    args = ap.parse_args(argv)
    return {"show": cmd_show, "lint": cmd_lint}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
