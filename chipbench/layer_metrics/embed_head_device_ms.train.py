"""ops / kernels: device ms a train step under ``mxtpu.embed`` (the
three embeddings, their norm and dropout), ``mxtpu.head`` (pooler, MLM
transform and tied decoder, NSP classifier) and ``mxtpu.loss``, forward +
backward."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_scope_ms(obs, "mxtpu.embed", "mxtpu.head", "mxtpu.loss")
