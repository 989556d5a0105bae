"""serving: mean over the window's rounds of ``step()["active"]`` (slots
holding a request after the round) over the total slots, in %."""
from chipbench.harness import stats


def read(obs):
    t0, t1 = obs["window"]
    a = [r["active"] for r in obs["rounds"] if t0 <= r["t0"] and r["t1"] <= t1]
    return 100.0 * stats.mean(a) / obs["slots"] if a else None
