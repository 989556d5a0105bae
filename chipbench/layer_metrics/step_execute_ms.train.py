"""trainer: median host time per step in ``mxtpu.trainer.execute``:
the call of the step's executable (jax's handling of every argument and
the enqueue; the program itself runs on the device afterwards).
The program's own span, read from the profiler's trace: traced runs only,
and None where the program has no such span."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_ms(obs, "mxtpu.trainer.execute")
