"""trainer: median host time inside one ``trainer.step()`` call that
reads nothing back (the benchmark's ``bench.step_call`` span)."""
from chipbench.harness import readers


def read(obs):
    return readers.window_span_ms(obs, "bench.step_call")
