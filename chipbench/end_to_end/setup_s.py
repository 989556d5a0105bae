"""Process start to the window's first edge: imports, weights, compiling
or loading every program, warm-up and (serving) the ramp."""


def read(obs):
    return obs["setup_s"]
