"""batch x seq x steps finished in the window over the window's length,
both edges closed by ``block_until_ready``; global over the cell's chips."""


def read(obs):
    if "tokens_per_step" not in obs:
        return None
    t0, t1 = obs["window"]
    return obs["tokens_per_step"] * obs["steps"] / (t1 - t0)
