"""trainer: device ms a train step under ``mxtpu.step.optimizer``: the
fused optimizer rule over every trainable parameter and its state
(``parallel/trainer.py`` ``_apply_rule``)."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_scope_ms(obs, "mxtpu.step.optimizer")
