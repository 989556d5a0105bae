"""Device time by program and by ``mxtpu.*`` scope, read back from the
profiler's trace of a ``--trace 1`` run by the PROGRAM's own reader.

``mxnet_tpu.profiler.device_dumps`` joins the trace's ``XLA Ops`` events
to the scope each instruction carries in the compiled text of the
executables the process still holds (which only the process itself can
do without a dump flag).  ``of(obs)`` is what the metric files call: None
unless the run was traced (an untraced run opens no file and renders no
HLO text), else ONE call of that reader on the newest ``.xplane.pb``
under ``<checkout>/.chipbench/trace/``, kept in ``obs`` and printed once
as the earlier line ``device_scopes``: per program its runs, median ms a
run, share of the traced busy time, and per scope forward / backward ms
a run, the part of it inherited by instructions the compiler inserted,
and the five instructions that took most of it.  A program without the
reader (the parent of the PR that added it) gives None, and every metric
that reads it is left out of the line; a reader that is there and RAISES
fails the traced run, so a broken reader cannot pass for an untraced run.

The 15 metrics that read it (``step_device_ms.train`` to
``attend_roofline_share.mla``) are entries of ``BENCHMARK.json`` like any
other; the first of their readers that a traced run calls reads the table.
(``chipbench/run_held.py``, their side entry until PR 39, stays only while
documents outside the benchmark name it, and adds nothing.)

The rest works on that table alone, so ``chipbench/tests`` checks it on a
hand-built one.
"""
import json

from . import program_spans, runtime

KEY = "device_scopes"          # where ``of`` keeps its result in ``obs``
STEP = "jit_full"              # the fused train step's module(s)
DECODE = "jit_decode_"         # a bucket's decode program
PREFILL = "jit_prefill_"       # a bucket's prefill program
UNNAMED = ("(no scope)", "(unknown program)")


def read_table():
    """The program's own table, or None where it has no such reader."""
    from mxnet_tpu import profiler
    dumps = getattr(profiler, "device_dumps", None)
    if dumps is None:
        return None
    return json.loads(dumps(logdir=program_spans.TRACE_ROOT))


def of(obs):
    if not obs.get("trace"):
        return None
    if KEY not in obs:
        obs[KEY] = read_table()
        if obs[KEY] is not None:
            runtime.emit(device_scopes=obs[KEY])
    return obs[KEY]


# -- reductions over the table ------------------------------------------------

def programs(table, prefix):
    """The programs whose module name starts with ``prefix`` and that ran
    whole at least once in the trace."""
    return [p for name, p in table["programs"].items()
            if name.startswith(prefix) and p["runs"]]


def scope_ms(program, *prefixes):
    """ms a run of ``program`` under the scopes named by ``prefixes``: a
    scope counts where it IS a prefix or lies below it
    (``mxtpu.mixer`` takes ``mxtpu.mixer.swa`` and
    ``mxtpu.mixer.mla.attend``; ``mxtpu.mlp`` does not take
    ``mxtpu.mlpx``)."""
    return sum(row["ms_per_run"] for scope, row in program["scopes"].items()
               if any(scope == p or scope.startswith(p + ".")
                      for p in prefixes))


def per_run(progs, value):
    """``value(program)`` averaged over the RUNS of ``progs`` (two
    buckets' decode programs: what one decode dispatch takes)."""
    runs = sum(p["runs"] for p in progs)
    return sum(value(p) * p["runs"] for p in progs) / runs if runs else None


def step_program(obs):
    """The fused train step: the ``jit_full*`` program that ran most."""
    table = of(obs)
    progs = programs(table, STEP) if table else []
    return max(progs, key=lambda p: p["runs"]) if progs else None


def step_ms(obs):
    """Median device ms of one run of the fused train step."""
    step = step_program(obs)
    return None if step is None else step["ms_per_run"]


def step_scope_ms(obs, *prefixes):
    step = step_program(obs)
    return None if step is None else scope_ms(step, *prefixes)


def run_ms(obs, prefix):
    """Median device ms of one run, averaged over the runs of the
    programs named ``prefix``."""
    table = of(obs)
    return per_run(programs(table, prefix), lambda p: p["ms_per_run"]) \
        if table else None


def decode_scope_ms(obs, *prefixes):
    table = of(obs)
    return per_run(programs(table, DECODE),
                   lambda p: scope_ms(p, *prefixes)) if table else None


def busy_share(obs, prefix):
    """% of the traced device-busy time inside runs of the programs
    named ``prefix``."""
    table = of(obs)
    if not table or not table["busy_ms"]:
        return None
    return 100.0 * sum(p["busy_share"] for name, p
                       in table["programs"].items()
                       if name.startswith(prefix))


def unscoped_share(obs):
    """% of the traced device-busy time that no ``mxtpu.*`` scope names:
    ops that carry none, programs with no live executable, ops outside
    any program's run.  The instrument's own coverage, AFTER the map
    gave the instructions the compiler inserted (``copy-done``,
    ``slice-done``: they carry no name of their own) the scope of their
    first scoped consumer; each scope's ``inherited_ms`` in the table
    says how much of its time is of that kind."""
    table = of(obs)
    if not table or not table["busy_ms"]:
        return None
    named = sum(row["ms_per_run"] * max(p["runs"], 1)
                for p in table["programs"].values()
                for scope, row in p["scopes"].items()
                if scope not in UNNAMED and p["runs"])
    return 100.0 * (1.0 - named / table["busy_ms"])
