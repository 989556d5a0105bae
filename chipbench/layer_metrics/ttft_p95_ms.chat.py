"""serving: wait for the first token FROM THE TIME THE REQUEST WAS DUE (not from
when the generator got round to submitting it), 95th percentile over the
requests due in the window.  A request that never got a token waited at
least until the run gave up on it.  What a chat user feels first; a
per-layer metric and not an end-to-end one because it spreads 7% from
run to run, more than any bound the contract admits could judge."""
from chipbench.harness import readers, stats


def read(obs):
    waits = readers.first_token_waits(obs)
    return stats.percentile(waits, 95) * 1e3 if waits else None
