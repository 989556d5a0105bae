"""serving: % of the traced device-busy time inside runs of the
``jit_prefill_*`` programs: what batched or chunked prefill (ROADMAP S1)
is priced by."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.busy_share(obs, device_scopes.PREFILL)
