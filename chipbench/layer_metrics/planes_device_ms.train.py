"""trainer: device ms a train step under ``mxtpu.step.health`` (the
health vector's reductions and the skip gate) and
``mxtpu.step.integrity`` (the fingerprint rows): what the planes inside
the step cost the device."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_scope_ms(obs, "mxtpu.step.health", "mxtpu.step.integrity")
