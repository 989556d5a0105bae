"""ops / kernels: device ms a train step under ``mxtpu.mlp`` (the
position-wise FFN with its activation, dropout, residual add and norm),
forward + backward."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_scope_ms(obs, "mxtpu.mlp")
