"""serving: mean seconds per admission (batch-1 prefill dispatch + first
token), from the program's ``mxtpu_serving_prefill_seconds`` histogram:
its sum over its count, between the warm-up's end and the run's."""


def read(obs):
    h0, h1 = obs["prefill_hist"]
    n = h1["count"] - h0["count"]
    return (h1["sum"] - h0["sum"]) / n * 1e3 if n else None
