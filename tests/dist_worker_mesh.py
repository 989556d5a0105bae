"""Worker: 2 processes x 4 virtual devices each — the pod shape.

A real pod composes TWO transport layers: ICI between chips of one
host's slice, DCN between hosts. The single-device-per-process dist
tests exercise only the cross-process hop; this worker builds ONE mesh
whose outer axis crosses the process (DCN-analog) boundary and whose
inner axis stays in-process (ICI-analog), and asserts collectives
reduce across both, separately and composed (VERDICT r2, next #6;
reference: dist_sync_kvstore.py run on multi-GPU hosts, SURVEY.md
§2.3 dist_sync_device / §3.5).

Run through ``tools/launch.py -n 2 python tests/dist_worker_mesh.py``.
"""
import os
import sys

# 4 virtual CPU devices per process (the ICI analog) — must be set
# before jax initializes its backends
_flags = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "host_platform_device_count" not in f)
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

import numpy as np

import mxnet_tpu as mx  # noqa: F401  joins the MXTPU_DIST_* rendezvous


def main():
    import jax.lax as lax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental import multihost_utils

    rank = jax.process_index()
    n_proc = jax.process_count()
    assert n_proc == 2, n_proc
    assert len(jax.local_devices()) == 4, jax.local_devices()
    assert len(jax.devices()) == 8, jax.devices()

    devs = np.array(sorted(
        jax.devices(), key=lambda d: (d.process_index, d.id)))
    devs = devs.reshape(2, 4)
    for r in range(2):
        assert all(d.process_index == r for d in devs[r]), \
            "outer mesh axis must cross the process boundary"
    mesh = Mesh(devs, ("dcn", "ici"))

    # per-device distinct values 1..8: process r contributes row r
    local = np.asarray([[rank * 4 + i + 1.0 for i in range(4)]],
                       np.float32)
    gx = multihost_utils.host_local_array_to_global_array(
        local, mesh, P("dcn", "ici"))

    # 1. psum composed across BOTH boundaries
    f = jax.jit(shard_map(
        lambda v: lax.psum(lax.psum(v, "ici"), "dcn"),
        mesh=mesh, in_specs=P("dcn", "ici"), out_specs=P()))
    got = np.asarray(f(gx).addressable_data(0))
    np.testing.assert_allclose(got, 36.0)  # sum(1..8)
    print(f"PSUM_BOTH_OK rank={rank}", flush=True)

    # 2. axis separation: reduce only in-process (ici), leave the
    # dcn axis varying — each process must see ITS row's sum
    g = jax.jit(shard_map(
        lambda v: lax.psum(v, "ici"),
        mesh=mesh, in_specs=P("dcn", "ici"), out_specs=P("dcn", None)))
    row = np.asarray(g(gx).addressable_data(0))
    want = 10.0 if rank == 0 else 26.0
    np.testing.assert_allclose(row, want)
    print(f"PSUM_ICI_OK rank={rank}", flush=True)

    # 3. all_gather across dcn after an in-process reduce: the
    # DCN-analog hop carries the ici-reduced partials, the shape a
    # hierarchical (reduce-scatter-in-slice, gather-across-hosts)
    # gradient exchange has
    # check_vma=False: all_gather output is value-replicated over dcn
    # but the vma system types it varying
    h = jax.jit(shard_map(
        lambda v: lax.all_gather(lax.psum(v, "ici"), "dcn", axis=0,
                                 tiled=True),
        mesh=mesh, in_specs=P("dcn", "ici"), out_specs=P(None, "ici"),
        check_vma=False))
    both = np.asarray(h(gx).addressable_data(0)).reshape(-1)
    np.testing.assert_allclose(sorted(both), [10.0, 26.0])
    print(f"MESH_OK rank={rank}/{n_proc}", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
