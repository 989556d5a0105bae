"""Worker: ONE dp×tp×pp training step on a 2-proc × 8-device mesh.

VERDICT r3 next #8: the parallelism axes are exercised separately
elsewhere (dp×tp fused trainer, sp ring, ep MoE, pp schedules, and a
2-proc dp mesh); this worker composes THREE axes in one compiled
program on the pod shape — dp=2 crossing the process boundary
(DCN-analog), tp=2 and pp=4 in-process (ICI-analog):

  * 4 pipeline stages over ``pp`` with a GPipe microbatch ring
    (``lax.ppermute`` carries activations stage-to-stage);
  * each stage's matmul column-sharded over ``tp`` with an
    ``all_gather`` restoring the activation;
  * per-dp-shard gradients exchanged with the INT8-wire
    ``quantized_psum`` over ``dp`` (compression on the dp axis), then
    an SGD update — all inside one shard_map.

Asserted against a single-device reference running the same math:
step-1 loss is exact (compression touches only the update), the
3-step loss trajectory tracks within int8-update tolerance and
decreases, and the LOWERED program carries i8 on the dp wire.

Reference analog: dist_sync_device — intra-host device reduce composed
with the inter-host sync (SURVEY.md §2.3).
Run via ``tools/launch.py -n 2 python tests/dist_worker_composed.py``.
"""
import os
import sys

if __name__ == "__main__":
    # worker-script mode only: a LIBRARY import (dryrun_multichip
    # reuses _composed_step) must not stomp the host process's
    # XLA_FLAGS/JAX_PLATFORMS
    _flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax

import numpy as np

import mxnet_tpu as mx  # noqa: F401  joins the MXTPU_DIST_* rendezvous
from jax.lax import axis_size as _axis_size

H = 8          # feature width
PP = 4         # pipeline stages
TP = 2
DP = 2
BATCH = 16     # global; per-dp shard 8 → 4 microbatches of 2
LR = 0.05


def _pipelined_local_loss(w_loc, x_loc, y_loc):
    """This device's half-batch loss through the tp-sharded pipeline.

    Runs INSIDE shard_map with pp/tp collectives only (dp stays
    un-reduced so per-shard grads exist for the compressed exchange).
    w_loc: (H, H/TP) this device's stage+column shard."""
    import jax.numpy as jnp
    import jax.lax as lax

    n = _axis_size("pp")
    p = lax.axis_index("pp")
    m = n                             # microbatches = stages
    mb = x_loc.shape[0] // m
    xs = x_loc.reshape(m, mb, H)
    ys = y_loc.reshape(m, mb, H)
    carry = jnp.zeros((mb, H), x_loc.dtype)
    outs = jnp.zeros((m, mb, H), x_loc.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for r in range(m + n - 1):
        mb_idx = r - p
        active = (mb_idx >= 0) & (mb_idx < m)
        # stage 0 injects a fresh microbatch; later stages consume the
        # ppermute carry from their predecessor
        x_in = jnp.where(p == 0, xs[min(r, m - 1)], carry)
        h_part = jnp.tanh(x_in @ w_loc)               # (mb, H/TP)
        h_full = lax.all_gather(h_part, "tp", axis=1, tiled=True)
        out = jnp.where(active, h_full, carry)
        # the LAST stage banks its finished microbatch
        slot = min(max(r - (n - 1), 0), m - 1)
        outs = outs.at[slot].set(
            jnp.where(active & (p == n - 1), out, outs[slot]))
        carry = lax.ppermute(out, "pp", perm)
    loss_local = jnp.where(
        p == n - 1, ((outs - ys) ** 2).mean(), 0.0)
    return lax.psum(loss_local, "pp")


def _composed_step(w_loc, x_loc, y_loc):
    """loss + int8-compressed-dp SGD update, one program.

    dp size comes from the MESH (lax.axis_size) rather than module
    constants, so dryrun_multichip can reuse this function on a
    different mesh shape without patching module state."""
    import jax.numpy as jnp
    import jax.lax as lax
    from mxnet_tpu.parallel import collectives

    dp = _axis_size("dp")
    w2 = w_loc[0]                     # strip the sharded pp dim
    loss, g = jax.value_and_grad(_pipelined_local_loss)(
        w2, x_loc, y_loc)
    g_avg = collectives.quantized_psum(g, "dp") / dp
    w_new = w2 - LR * g_avg
    loss_mean = lax.psum(loss, "dp") / dp
    return loss_mean, w_new[None]


def _reference(w0, x, y, steps):
    """Single-device: same stages sequentially, full batch, exact SGD."""
    import jax.numpy as jnp

    def loss_fn(w):
        h = x
        for s in range(PP):
            h = jnp.tanh(h @ w[s])
        return ((h - y) ** 2).mean()

    w = jnp.asarray(w0)
    losses = []
    for _ in range(steps):
        loss, g = jax.value_and_grad(loss_fn)(w)
        losses.append(float(loss))
        w = w - LR * g
    return losses, np.asarray(w)


def main():
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental import multihost_utils

    rank = jax.process_index()
    assert jax.process_count() == 2
    assert len(jax.local_devices()) == 8
    devs = np.array(sorted(
        jax.devices(), key=lambda d: (d.process_index, d.id)))
    devs = devs.reshape(DP, TP, PP)
    for r in range(DP):
        assert all(d.process_index == r for d in devs[r].ravel()), \
            "dp must be the cross-process axis"
    mesh = Mesh(devs, ("dp", "tp", "pp"))

    rng = np.random.RandomState(0)
    w0 = (rng.rand(PP, H, H).astype("f") - 0.5) * 0.8
    x_np = rng.rand(BATCH, H).astype("f")
    y_np = np.tanh(rng.rand(BATCH, H).astype("f"))

    w_spec = P("pp", None, "tp")
    x_spec = P("dp", None)
    # host_local semantics: along a PROCESS-CROSSING axis each process
    # passes its LOCAL shard — rank r owns batch rows [r*8, r*8+8), so
    # the two dp shards carry DIFFERENT data and the dp reduce is
    # actually load-bearing (r4 review: identical shards would let a
    # broken dp exchange pass parity).  W has no dp axis: pp/tp are
    # in-process, so both processes pass the identical full array.
    half = BATCH // DP
    gw = multihost_utils.host_local_array_to_global_array(
        w0, mesh, w_spec)
    gx = multihost_utils.host_local_array_to_global_array(
        x_np[rank * half:(rank + 1) * half], mesh, x_spec)
    gy = multihost_utils.host_local_array_to_global_array(
        y_np[rank * half:(rank + 1) * half], mesh, x_spec)

    step = jax.jit(shard_map(
        _composed_step, mesh=mesh,
        in_specs=(w_spec, x_spec, x_spec),
        out_specs=(P(), w_spec), check_vma=False))

    # the dp gradient wire must be int8 in the LOWERED program —
    # anchored to the COLLECTIVE line: a stray i8 convert elsewhere
    # must not green-light an f32 wire
    import re
    txt = step.lower(gw, gx, gy).as_text()
    assert re.search(r"all_to_all[^\n]*i8", txt) or \
        re.search(r"all_gather[^\n]*i8", txt), \
        "no i8-carrying collective in the composed program"
    print(f"COMPOSED_I8_WIRE_OK rank={rank}", flush=True)

    ref_losses, ref_w = _reference(w0, x_np, y_np, 3)
    losses = []
    for _ in range(3):
        loss, gw = step(gw, gx, gy)
        losses.append(float(np.asarray(loss.addressable_data(0))))

    # step 1: compression only affects the UPDATE — loss is exact
    np.testing.assert_allclose(losses[0], ref_losses[0], rtol=1e-5)
    # later steps run on int8-updated weights: close, and decreasing
    for a, b in zip(losses[1:], ref_losses[1:]):
        np.testing.assert_allclose(a, b, rtol=0.1)
    assert losses[-1] < losses[0], losses
    print(f"COMPOSED_PARITY_OK rank={rank} losses="
          f"{[round(v, 5) for v in losses]}", flush=True)
    print(f"COMPOSED_OK rank={rank}/2", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
