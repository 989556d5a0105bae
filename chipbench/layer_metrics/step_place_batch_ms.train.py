"""trainer: median host time per step in ``mxtpu.trainer.place_batch``:
placing the step's batch on the device(s) (the ``_put_cached`` calls
and ``_prune_placed``).
The program's own span, read from the profiler's trace: traced runs only,
and None where the program has no such span."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_ms(obs, "mxtpu.trainer.place_batch")
