"""trainer: median host time per step in ``mxtpu.trainer.gather_args``:
collecting the step program's arguments (parameter buffers, update
counts, one ``np.asarray`` per optimizer scalar per parameter, state
buffers, health flags).
The program's own span, read from the profiler's trace: traced runs only,
and None where the program has no such span."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_ms(obs, "mxtpu.trainer.gather_args")
