"""trainer: ``engine.cache_info()["dispatches"]`` over the window by the
steps made in it.  The fused step's contract is exactly 1."""


def read(obs):
    c0, c1 = obs["counters"]
    return (c1["dispatches"] - c0["dispatches"]) / obs["steps"]
