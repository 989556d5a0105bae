"""serving: median over the ``mxtpu.serving.admit`` spans of the span
without its ``token_read`` child: the host's own time in one admission
(building the prompt, the dispatch path, the bookkeeping)."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_of_ms(obs, "admit_host_s")
