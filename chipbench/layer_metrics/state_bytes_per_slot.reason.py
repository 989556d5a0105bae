"""serving: bytes of state ONE slot holds, all kinds together: the sum of
the program's gauges ``mxtpu_serving_state_bytes_b<slots>x<prompt>_<kind>``
(set when a pool is built, from the model's ``state_spec``) over the
slots.  A program without the gauges gives None."""


def read(obs):
    from mxnet_tpu import telemetry
    total = sum(v for k, v in telemetry.snapshot()["gauges"].items()
                if k.startswith("mxtpu_serving_state_bytes_b"))
    return total / obs["slots"] if total and obs.get("slots") else None
