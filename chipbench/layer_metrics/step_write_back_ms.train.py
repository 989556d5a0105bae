"""trainer: median host time per step in ``mxtpu.trainer.write_back``:
writing the step's outputs back (``_set_data`` over aux, parameters and
optimizer state, the health sample, the loss's ``NDArray``).
The program's own span, read from the profiler's trace: traced runs only,
and None where the program has no such span."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_ms(obs, "mxtpu.trainer.write_back")
