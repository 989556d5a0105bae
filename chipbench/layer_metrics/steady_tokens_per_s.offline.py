"""serving: median over chunks of 25 consecutive rounds of the chunk's
output tokens over its time.  Now and then the whole process is held up
for seconds (PERF.md section 5); that weighs on ``serve_tokens_per_s``
in full and on this median not at all, so the two together tell a
slower server from a run that was held up."""
from chipbench.harness import readers


def read(obs):
    return readers.steady_tokens_per_s(obs)
