"""One thread drives ``serving.Server`` as its users do, open or closed.

Each round: submit every request now due, ``srv.step()``, stamp every
live request's new tokens (the first with the server's own
``first_token_t``, the rest with the round's end), repeat.  The traffic
file's ``arrivals`` says who sends: ``open`` is a schedule at a fixed
rate that does not wait for the server (time is counted from when a
request was DUE); ``closed`` is N clients that each send their next
request when the last one finished.  A ramp of the same traffic before
the window is part of set-up; after the window the loop drains for at
most ``drain_s``, and what has not finished by then has failed.
"""
import time

import numpy as np

from chipbench.harness import readers, runtime, stats, traffic as gen
from mxnet_tpu.base import MXNetError


class Loop:
    """The serving loop and its log: requests with every token's arrival
    time, rounds with what they did."""

    def __init__(self, srv, spans):
        self.srv, self.spans = srv, spans
        self.live = []            # records still queued or holding a slot
        self.requests = []        # every record ever submitted
        self.rounds = []
        self.refused = 0

    def submit(self, prompt, new_tokens, due, client=None):
        rec = {"due": due, "asked": int(new_tokens), "stamps": [],
               "client": client, "done": None, "req": None,
               "prompt_len": len(prompt)}
        with self.spans("bench.submit"):
            rec["submit"] = time.perf_counter()
            try:
                rec["req"] = self.srv.submit(prompt,
                                             max_new_tokens=new_tokens)
            except MXNetError as e:   # refused: counts as failed
                rec["error"] = repr(e)[:200]
                self.refused += 1
        self.requests.append(rec)
        if rec["req"] is not None:
            self.live.append(rec)
        return rec

    def round(self):
        """One ``srv.step()``; returns the records that finished in it."""
        busy = sum(1 for b in self.srv.sched.buckets if b.n_active())
        t0 = time.perf_counter()
        with self.spans("bench.server_step"):
            st = self.srv.step()
        t1 = time.perf_counter()
        self.rounds.append({"t0": t0, "t1": t1, "admitted": st["admitted"],
                            "active": st["active"], "queued": st["queued"],
                            "tokens": st["tokens"], "busy_before": busy})
        finished, still = [], []
        for rec in self.live:
            req = rec["req"]
            have = len(rec["stamps"])
            new = len(req.generated) - have
            if new:
                if have == 0:
                    rec["stamps"].append(req.first_token_t)
                    new -= 1
                rec["stamps"].extend([t1] * new)
            if req.state == "done":
                rec["done"] = req.done_t
                finished.append(rec)
            elif req.state in ("queued", "active"):
                still.append(rec)
        self.live = still
        return finished

    def drain(self, limit_s):
        t_end = time.perf_counter() + limit_s
        while self.live and time.perf_counter() < t_end:
            self.round()


def _phase(loop, run, stream, arrivals, seconds, ramp_s, drain_s, tracer):
    """Ramp, window, drain.  The window is fixed beforehand as
    [now + ramp_s, + seconds): returns (t_w0, t_w1).  ``tracer`` is None
    in a sweep, whose phases open no measured window."""
    t_begin = time.perf_counter()
    t_w0 = t_begin + ramp_s
    t_w1 = t_w0 + seconds
    if tracer is not None:
        run.setup_s = t_w0 - run.t_process
        run.setup["ramp"] = ramp_s
    if arrivals["kind"] == "open":
        due = gen.arrival_times(
            float(arrivals["rate_per_s"]), arrivals["gaps"],
            int(arrivals["block"]), gen.rng_for(run.seed, 4), t_begin, t_w1)
        idle_clients = []
    else:
        due = []
        idle_clients = list(range(int(arrivals["clients"])))
    nxt = 0
    give_up = None
    while True:
        now = time.perf_counter()
        # every request due in the window is sent, however late the loop is
        while nxt < len(due) and due[nxt] <= now:
            p, n = next(stream)
            loop.submit(p, n, due[nxt])
            nxt += 1
        if now < t_w1:
            for c in idle_clients:
                p, n = next(stream)
                loop.submit(p, n, now, client=c)
            idle_clients = []
            if not loop.live:     # nothing to do until the next arrival
                wait = (due[nxt] if nxt < len(due) else t_w1) - now
                time.sleep(max(0.0, min(wait, 0.002)))
                continue
        else:
            if give_up is None:
                if tracer is not None:
                    tracer.stop()     # after the closing edge, not inside
                give_up = time.perf_counter() + drain_s
            if not loop.live or now >= give_up:
                return t_w0, t_w1
        for rec in loop.round():
            if rec["client"] is not None:
                idle_clients.append(rec["client"])
        if tracer is not None:
            tracer.tick(time.perf_counter() - t_w0)


def _mark_counted(loop, t_w0, t_w1, closed):
    for rec in loop.requests:
        t = rec["submit"] if closed else rec["due"]
        rec["counted"] = t_w0 <= t < t_w1


def _summary(loop, t_w0, t_w1, t_end):
    """What a phase did, for the earlier lines and the sweep's table: the
    same reductions the metric readers use."""
    obs = {"requests": loop.requests, "window": (t_w0, t_w1), "t_end": t_end}
    cnt = readers.counted(obs)
    ttft = readers.first_token_waits(obs)
    gaps = readers.inter_token_gaps(obs)
    rounds = [r for r in loop.rounds if t_w0 <= r["t0"] and r["t1"] <= t_w1]
    ms = 1e3
    # where a run lost time: a round of over a second inside the window
    # is no round of the server but the whole process held up (PERF.md
    # section 5)
    obs["rounds"] = loop.rounds
    long = [r for r in loop.rounds
            if r["t1"] - r["t0"] > 1.0 and r["t1"] > t_w0 and r["t0"] < t_w1]
    return {
        "held_up_s": sum(min(r["t1"], t_w1) - max(r["t0"], t_w0)
                         for r in long),
        "held_up_at_s": [r["t0"] - t_w0 for r in long],
        "round_max_ms": max((r["t1"] - r["t0"] for r in rounds),
                            default=0) * ms,
        "steady_tokens_per_s": readers.steady_tokens_per_s(obs) or 0,
        "due": len(cnt),
        "done": sum(1 for r in cnt if r["done"] is not None
                    and len(r["stamps"]) == r["asked"]),
        "queued_at_end": next((r["queued"] for r in reversed(loop.rounds)
                               if r["t1"] <= t_w1), 0),
        "ttft_p50_ms": (stats.median(ttft) or 0) * ms,
        "ttft_p95_ms": (stats.percentile(ttft, 95) or 0) * ms,
        "itl_p50_ms": (stats.median(gaps) or 0) * ms,
        "itl_p95_ms": (stats.percentile(gaps, 95) or 0) * ms,
        "tokens_per_s": readers.tokens_in_window(obs) / (t_w1 - t_w0),
        "late_p95_ms": (stats.percentile(
            readers.generator_lateness(obs), 95) or 0) * ms,
        "rounds": len(rounds),
        "round_p50_ms": (stats.median(
            [r["t1"] - r["t0"] for r in rounds]) or 0) * ms,
        "admitted": sum(r["admitted"] for r in rounds),
        "mean_active": stats.mean([r["active"] for r in rounds]),
    }


# How long set-up waits for the requests that make the first call of every
# program.  Their first rounds COMPILE in a run that finds no cache (a
# bucket's decode and every rung of its prefill ladder): 121-124 s for the
# probe of ``reason_closed`` and ``longgen_closed`` (my chip run, PR 39,
# call 1), which the former 120 s cut after one token, so that a sound run
# read ``correct`` false.  Only a server that never answers waits it out.
FIRST_CALLS_LIMIT_S = 600.0


def _probe(run, net, srv, ctx, builder, loop):
    """The first request of every run: its greedy tokens against a plain
    full-sequence forward of the same weights (chip_smoke.py's rule: each
    served token is the reference's argmax, or within ``gap_share`` of the
    row's largest |logit| of it).  One fixed shape, compiled once."""
    pr = run.traffic["probe"]
    vocab = int(run.shapes["vocab_size"])
    prompt = gen.rng_for(run.seed, 5).integers(
        1, vocab, int(pr["prompt_len"])).astype(np.float32)
    rec = loop.submit(prompt, int(pr["new_tokens"]), time.perf_counter())
    loop.drain(FIRST_CALLS_LIMIT_S)
    req = rec["req"]
    run.checks.hold(req is not None and len(req.generated)
                    == int(pr["new_tokens"]),
                    f"the probe produced {len(req.generated)} tokens")
    toks = req.tokens()
    logits = builder.full_forward_logits(net, toks[:-1], ctx)
    exact, regrets = 0, []
    for i, tok in enumerate(req.generated):
        row = logits[req.prompt_len - 1 + i]
        exact += int(np.argmax(row) == tok)
        regrets.append(float((row.max() - row[tok]) / np.abs(row).max()))
    run.checks.hold(max(regrets) <= float(pr["gap_share"]),
                    f"a served greedy token is {max(regrets):.4f} of the "
                    f"largest |logit| below the full forward's best "
                    f"(allowed {pr['gap_share']})")
    return {"probe_tokens": len(regrets), "probe_exact_argmax": exact,
            "probe_worst_regret_share": max(regrets)}


def _prefill_hist():
    """The program's own histogram of admissions: {"sum", "count", ...}."""
    from mxnet_tpu import telemetry
    return telemetry.histogram(
        "mxtpu_serving_prefill_seconds",
        "one admission (prefill dispatch + first token) (s)").summary()


def run(run):
    import jax
    from mxnet_tpu import telemetry

    tr, shapes, checks = run.traffic, run.shapes, run.checks
    builder = runtime.builder_for(run)
    vocab = int(shapes["vocab_size"])
    with run.setup_phase("import_and_weights"):
        net, srv, ctx = builder.build_server(
            shapes, run.seed, run.devices[0], int(tr["max_queue"]))
        jax.block_until_ready([p.data(ctx)._data
                               for p in net.collect_params().values()])
    loop = Loop(srv, run.spans)

    # first call of every program: the probe warms the bucket its prompt
    # falls in, one 2-token request each warms the others
    with run.setup_phase("first_calls_probe"):
        probe = _probe(run, net, srv, ctx, builder, loop)
    with run.setup_phase("first_calls_other_buckets"):
        rng = gen.rng_for(run.seed, 6)
        for n in tr["warm_prompt_lens"]:
            loop.submit(rng.integers(1, vocab, int(n)).astype(np.float32),
                        2, time.perf_counter())
        loop.drain(FIRST_CALLS_LIMIT_S)
    warm_requests = len(loop.requests)
    runtime.hold_on_platform(
        checks, [(p.name, p.data(ctx)._data)
                 for p in net.collect_params().values()]
        + [(f"page[{k}][{i}]", c) for k, pool in srv._pools.items()
           for i, c in enumerate(pool.flat())],
        run.devices[0].platform, "weight or KV page")

    stream = gen.request_stream(tr, vocab, run.seed)
    arrivals = tr["arrivals"]
    closed = arrivals["kind"] == "closed"
    slots = sum(b.slots for b in srv.sched.buckets)

    if run.sweep:
        for rate in (float(x) for x in run.sweep.split(",")):
            loop.requests, loop.rounds = [], []
            t_w0, t_w1 = _phase(loop, run, stream,
                                dict(arrivals, rate_per_s=rate),
                                run.seconds, float(tr["ramp_s"]),
                                float(tr["drain_s"]), None)
            _mark_counted(loop, t_w0, t_w1, closed)
            s = _summary(loop, t_w0, t_w1, time.perf_counter())
            runtime.emit(sweep_rate_per_s=rate, slots=slots, sustained=(
                s["queued_at_end"] <= slots
                and s["done"] >= 0.99 * s["due"]), **s)
            loop.drain(60.0)
        return None

    telemetry.clear_events()
    pre0 = _prefill_hist()
    loop.requests, loop.rounds = [], []
    c0 = runtime.program_counters()
    t_w0, t_w1 = _phase(loop, run, stream, arrivals, run.seconds,
                        float(tr["ramp_s"]), float(tr["drain_s"]),
                        run.tracer)
    t_end = time.perf_counter()
    c1 = runtime.program_counters()
    pre1 = _prefill_hist()
    _mark_counted(loop, t_w0, t_w1, closed)

    # -- correct ----------------------------------------------------------
    cnt = [r for r in loop.requests if r["counted"]]
    failed = [r for r in cnt if r["done"] is None
              or len(r["stamps"]) != r["asked"]]
    wrong = [r for r in loop.requests if r["done"] is not None
             and len(r["req"].generated) != r["asked"]]
    checks.hold(not wrong, f"{len(wrong)} finished requests do not have "
                           "the tokens they asked for")
    # one prefill dispatch per admission; one decode dispatch per bucket
    # that holds a request when the round decodes (a bucket that was
    # empty before a round's admissions may or may not be filled by them)
    d = c1["dispatches"] - c0["dispatches"]
    admitted = sum(r["admitted"] for r in loop.rounds)
    lo = admitted + sum(r["busy_before"] for r in loop.rounds)
    hi = admitted + sum(r["busy_before"] if not r["admitted"]
                        else len(srv.sched.buckets) for r in loop.rounds)
    checks.hold(lo <= d <= hi,
                f"{d} dispatches over {len(loop.rounds)} rounds and "
                f"{admitted} admissions; expected {lo}..{hi}")
    for k in ("fresh_compiles", "aot_demotions"):
        checks.hold(c1[k] == c0[k],
                    f"{k} rose by {c1[k] - c0[k]} after the warm-up")
    for key, s in srv.stats()["buckets"].items():
        checks.hold(s["steady_misses"] == 0
                    and s["steady_fresh_compiles"] == 0,
                    f"bucket {key} kept compiling in steady state: {s}")
    runtime.hold_no_events(checks, "after the warm-up")

    summary = _summary(loop, t_w0, t_w1, t_end)
    runtime.emit(serve=run.workload["name"], arrivals=arrivals, slots=slots,
                 params=builder.n_params(net), warm_requests=warm_requests,
                 refused=loop.refused, dispatches=d,
                 dispatches_expected=[lo, hi], **probe, **summary,
                 prompt_lens_first=[r["prompt_len"] for r in cnt[:12]],
                 asked_first=[r["asked"] for r in cnt[:12]],
                 bucket_stats=srv.stats()["buckets"])
    return {"attempted": len(cnt), "failed": len(failed),
            "window": (t_w0, t_w1), "t_end": t_end,
            "requests": loop.requests,
            "rounds": loop.rounds, "slots": slots,
            "prefill_hist": (pre0, pre1), "counters": (c0, c1),
            "spans": run.spans}
