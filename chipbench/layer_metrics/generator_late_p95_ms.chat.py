"""entry points (the benchmark's own generator): 95th percentile of
actual ``submit`` time minus due time.  A starved generator must not be
read as a fast server."""
from chipbench.harness import readers, stats


def read(obs):
    late = readers.generator_lateness(obs)
    return stats.percentile(late, 95) * 1e3 if late else None
