"""serving: stutter, the 95th percentile over EVERY gap between consecutive output
tokens of the requests due in the window, as the host sees them arrive.
Per-layer and not end-to-end for the reason in ``ttft_p95_ms.chat``: the
gaps have modes (a round with 0, 1, 2 admissions) and the p95 jumps
between them."""
from chipbench.harness import readers, stats


def read(obs):
    gaps = readers.inter_token_gaps(obs)
    return stats.percentile(gaps, 95) * 1e3 if gaps else None
