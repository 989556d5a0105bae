"""ops / kernels: an END-TO-END utilization, not a kernel's roofline
share: this run's samples/s x bench.py's v2 FLOPs per sample over
(chips x the device_kind's bf16 peak in chipbench/peaks.json)."""


def read(obs):
    if obs["peaks"] is None:
        return None
    t0, t1 = obs["window"]
    samples_per_s = obs["samples_per_step"] * obs["steps"] / (t1 - t0)
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * samples_per_s * obs["flops_per_sample"] / peak
