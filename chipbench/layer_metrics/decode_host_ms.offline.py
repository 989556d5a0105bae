"""serving: median, over the ``mxtpu.serving.round`` spans that admitted
nothing, of the round's ``mxtpu.serving.decode`` spans without their
``token_read`` child (in which the host only waits for the device): the
host's own time in a decode-only round, all its buckets together."""
from chipbench.harness import program_spans


def read(obs):
    return program_spans.median_of_ms(obs, "decode_host_s")
