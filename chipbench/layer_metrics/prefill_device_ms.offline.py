"""serving: device ms of ONE prefill run (an admission): each
``jit_prefill_*`` program's median run, averaged over the buckets'
runs."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.run_ms(obs, device_scopes.PREFILL)
