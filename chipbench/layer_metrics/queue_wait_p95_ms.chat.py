"""serving: 95th percentile, over the requests due in the window, of the
wait for a slot: the program's own stamps ``Request.admit_t`` (where the
admission starts) minus ``Request.submit_t``.  Needs no trace; None where
the program keeps no ``admit_t``."""
from chipbench.harness import readers, stats


def read(obs):
    reqs = [r["req"] for r in readers.counted(obs) if r.get("req")]
    waits = [r.admit_t - r.submit_t for r in reqs
             if getattr(r, "admit_t", None) is not None]
    return stats.percentile(waits, 95) * 1e3 if waits else None
