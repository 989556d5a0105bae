"""Mesh-change array redistribution (checkpoint restore + live moves).

The paper trail is "Memory-efficient array redistribution through
portable collective communication" (arXiv:2112.01075, PAPERS.md): when
a job restarts on a different chip count or mesh shape, the saved
layout and the target layout differ and every array must move —
without a gather-to-host round trip when both layouts are live on
device.

Two cases land here:

* **live → live** (``redistribute``): source and target sharding are
  both device-resident.  When the two meshes cover the same device
  set, the move is ONE compiled identity program with pinned
  ``out_shardings`` — XLA lowers the layout change to the minimal
  all-gather / dynamic-slice / collective-permute program (the
  portable-collective formulation of 2112.01075 is what the SPMD
  partitioner implements).  Across different device sets,
  ``jax.device_put`` performs the transfer through the runtime's
  resharding machinery.
* **host → live** (checkpoint restore, ``place``): the shard files
  hold the full logical array; placement is a sharded ``device_put``
  onto the target spec — each device receives only its slice.

``plan`` renders the per-array move as a human-readable op list
(``all_gather(dp:8)``, ``slice(dp:4)``, ...) for telemetry and the
restore report; it is derived purely from (shape, src spec/mesh, dst
spec/mesh), never from device state.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["spec_from_str", "spec_to_str", "plan", "place",
           "redistribute", "plan_moves", "redistribute_plan"]


def spec_to_str(spec) -> str:
    """Canonical string form of a PartitionSpec (manifest field)."""
    return str(tuple(spec)) if spec is not None else "()"


def spec_from_str(text: Optional[str]):
    """Parse the manifest's sharding-spec string back into a
    ``PartitionSpec``.  Accepts the ``str(spec)`` /
    ``str(tuple(spec))`` forms the trainers record; unknown/empty
    forms mean "replicated"."""
    from jax.sharding import PartitionSpec as P
    if not text:
        return P()
    t = text.strip()
    if t.startswith("PartitionSpec"):
        t = t[len("PartitionSpec"):]
    t = t.strip()
    if t in ("", "()", "(,)"):
        return P()
    if not (t.startswith("(") and t.endswith(")")):
        raise MXNetError(f"unparseable sharding spec {text!r}")
    # the recorded form is str(tuple(spec)) — a python literal whose
    # entries are axis names, None, or TUPLES of axis names (a dim
    # sharded over several mesh axes), so a flat comma split cannot
    # parse it
    import ast
    try:
        val = ast.literal_eval(t)
    except (ValueError, SyntaxError):
        raise MXNetError(f"unparseable sharding spec {text!r}")
    if not isinstance(val, tuple):
        raise MXNetError(f"unparseable sharding spec {text!r}")
    for e in val:
        if not (e is None or isinstance(e, str) or
                (isinstance(e, tuple) and
                 all(isinstance(n, str) for n in e))):
            raise MXNetError(f"unparseable sharding spec {text!r}")
    return P(*val)


def _axis_factor(spec, mesh_axes: Dict[str, int]) -> Dict[int, Tuple]:
    """dim index -> (axis name, shard count) for the sharded dims."""
    out = {}
    for d, entry in enumerate(tuple(spec or ())):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for name in names:
            n *= int(mesh_axes.get(name, 1))
        out[d] = (names, n)
    return out


def plan(shape: Sequence[int], src_spec, src_mesh: Dict[str, int],
         dst_spec, dst_mesh: Dict[str, int]) -> List[str]:
    """The collective moves a (src layout) -> (dst layout) transition
    needs, as op strings.  Replicated->replicated across a size change
    is a pure broadcast/subset (``replicate``); a shrinking sharded dim
    all-gathers then re-slices; identical layouts are a no-op."""
    src = _axis_factor(src_spec, src_mesh)
    dst = _axis_factor(dst_spec, dst_mesh)
    steps: List[str] = []
    for d in sorted(set(src) | set(dst)):
        s = src.get(d)
        t = dst.get(d)
        if s == t and (s is None or
                       src_mesh.get(s[0][0]) == dst_mesh.get(s[0][0])):
            continue
        if s is not None:
            names, n = s
            steps.append(f"all_gather(dim={d}, "
                         f"{'x'.join(names)}:{n})")
        if t is not None:
            names, n = t
            steps.append(f"slice(dim={d}, {'x'.join(names)}:{n})")
    if not steps and dict(src_mesh) != dict(dst_mesh):
        steps.append(
            f"replicate({'x'.join(f'{k}:{v}' for k, v in dst_mesh.items())})")
    return steps


def place(host_array, mesh, spec):
    """Host array -> device array sharded per ``spec`` on ``mesh``
    (the checkpoint-restore leg: each device materializes its slice)."""
    import jax
    from jax.sharding import NamedSharding
    return jax.device_put(host_array, NamedSharding(mesh, spec))


def redistribute(arrays, target_shardings):
    """Move live device arrays onto ``target_shardings`` (one per
    array), on-device when possible.

    Same device set on both sides: ONE jitted identity with pinned
    ``out_shardings`` — the compiled all-gather/slice/permute program.
    Different device sets (a 4-chip restart inheriting 8-chip arrays):
    ``jax.device_put`` per array via the runtime's transfer engine.
    fp32-exact either way (layout moves never touch element values).
    """
    import jax
    arrays = list(arrays)
    targets = list(target_shardings)
    if not arrays:
        return []
    try:
        src_devs = {d for a in arrays for d in a.sharding.device_set}
        dst_devs = {d for s in targets for d in s.device_set}
    except AttributeError:
        src_devs, dst_devs = None, ()
    if src_devs is not None and src_devs == dst_devs:
        try:
            # every caller rebinds its holders to the moved arrays, so
            # the sources are dead on return: donate them, or the one-
            # program layout move transiently holds model+state twice
            moved = jax.jit(lambda *xs: xs,
                            out_shardings=tuple(targets),
                            donate_argnums=tuple(range(len(arrays)))
                            )(*arrays)
            return list(moved)
        except Exception:
            # compile-stage failures leave every input alive and the
            # per-array fallback below absorbs them; an EXECUTION
            # failure may have consumed the donated sources — the
            # fallback would then raise an unrelated deleted-array
            # error, so surface the true cause instead
            def _dead(a):
                try:
                    return a.is_deleted()
                except Exception:
                    return False
            if any(_dead(a) for a in arrays):
                raise
    return [jax.device_put(a, s) for a, s in zip(arrays, targets)]


# -- plan-to-plan redistribution (docs/parallelism.md, reshard matrix) ------

def plan_moves(named_shapes, plan_src, plan_dst,
               dtype_bytes: int = 4) -> Dict[str, dict]:
    """The per-param move report of a ``plan_src -> plan_dst``
    redistribution: ``{name: {"moves": [...], "nbytes": int}}`` for
    every param whose layout actually changes (``moves`` from
    :func:`plan`; ``nbytes`` is the GLOBAL tensor size — the upper
    bound on bytes the move touches).  Derived purely from shapes +
    the two plans, never from device state — the ``mxplan diff``
    accounting input."""
    out: Dict[str, dict] = {}
    for row in plan_src.diff(plan_dst, named_shapes,
                             dtype_bytes=dtype_bytes):
        out[row["name"]] = {"moves": row["moves"],
                            "nbytes": row["nbytes"],
                            "from_spec": row["from_spec"],
                            "to_spec": row["to_spec"]}
    return out


def redistribute_plan(named_arrays, plan_dst, mesh=None):
    """Move arrays saved/live under ANY source plan onto ``plan_dst``'s
    resolution — between any two plans, not just dp-size changes
    (fp32-exact: layout moves never touch element values).

    ``named_arrays``: ``[(param_path, array)]`` — live device arrays
    (the one-donated-program move of :func:`redistribute` when the
    device sets coincide) or host arrays (sharded ``device_put`` per
    :func:`place`).  ``mesh`` defaults to ``plan_dst.build_mesh()``.
    Returns the moved arrays in order.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    named_arrays = list(named_arrays)
    if mesh is None:
        mesh = plan_dst.build_mesh()
    targets = []
    for name, a in named_arrays:
        spec, _idx = plan_dst.spec_for(name, a.shape)
        targets.append(NamedSharding(mesh, P(*spec)))
    return redistribute([a for _n, a in named_arrays], targets)
