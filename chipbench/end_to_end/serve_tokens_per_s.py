"""Output tokens that arrived inside the window, of every request
whenever it was sent, over the window's length."""
from chipbench.harness import readers


def read(obs):
    if "requests" not in obs:
        return None
    t0, t1 = obs["window"]
    return readers.tokens_in_window(obs) / (t1 - t0)
