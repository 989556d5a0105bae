"""ops / kernels: device ms a decode run under ``mxtpu.mlp`` and
``mxtpu.moe.*`` (router, grouped products over the held experts, shared
expert, the norms and adds around them)."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.decode_scope_ms(obs, "mxtpu.mlp", "mxtpu.moe")
