"""ops / kernels: device ms a train step under ``mxtpu.mixer.*`` (BERT:
``mxtpu.mixer.full``: the four projections, the attention product, its
dropout, the residual add and the norm after it), forward + backward."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_scope_ms(obs, "mxtpu.mixer")
