"""engine + tiers: median ``mxtpu.engine.lookup`` span, the cache key and
the executable's lookup of one ``engine.invoke_compiled``, in
microseconds (traced runs only)."""
from chipbench.harness import program_spans


def read(obs):
    ms = program_spans.median_ms(obs, "mxtpu.engine.lookup")
    return None if ms is None else ms * 1e3
