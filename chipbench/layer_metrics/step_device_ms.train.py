"""trainer: device ms of ONE run of the fused train step's program
(``jit_full*``): the median length of its ``XLA Modules`` events in the
traced seconds, from the program's own reader
(``mxnet_tpu.profiler.device_dumps``).  Traced runs only; None where the
program has no such reader."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.step_ms(obs)
