"""ops / kernels: device ms a decode run under ``mxtpu.mixer.*``:
attention and state-space mixers of every kind with their projections,
norms, masks and page writes."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.decode_scope_ms(obs, "mxtpu.mixer")
