"""trainer: median SELF time of ``mxtpu.trainer.step``, the part of a
step that none of its phase spans covers.  Closure: under a tenth of
``step_call_ms.train``, or a phase is missing its span."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_ms(obs, "mxtpu.trainer.step",
                                   self_time=True)
