"""serving: device ms of ONE decode program run: each ``jit_decode_*``
program's median run, averaged over the buckets' runs (a round runs one
a busy bucket)."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.run_ms(obs, device_scopes.DECODE)
