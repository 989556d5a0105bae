"""SPMD Llama: sharded checkpoint -> tp×pp mesh -> pipelined fine-tune.

The seam-composition layer VERDICT r4 #3 asked for: the pieces existed
separately (``hf_loader`` sharded index, ``parallel.planning`` tp×pp
layout, ``chunked_softmax_ce``, the 1F1B pipeline) — this module makes
them one story:

  * :func:`load_llama_stacked` reads an HF-layout (possibly sharded)
    safetensors checkpoint STRAIGHT onto a ``(tp, pp)`` device mesh via
    ``jax.make_array_from_callback``: each device's addressable shard is
    read from the zero-copy mmap view of exactly the bytes it owns —
    the full model is never materialized on the host (the multi-host
    contract; on a single host the page cache sees every byte but no
    full-tensor ndarray is ever built).  Layer weights come back
    STACKED over a leading stage axis sharded over ``pp`` (the jax
    pipeline layout), Megatron column/row-sharded over ``tp`` per
    ``parallel.planning.llama_param_rule``'s taxonomy.
  * :func:`make_stage_fn` is the functional decoder layer (RMSNorm →
    GQA attention with adjacent-pair RoPE → SwiGLU) that runs INSIDE
    ``parallel.pipeline_apply`` / ``pipeline_value_and_grad`` with
    ``lax.psum`` over ``tp`` closing the row-parallel projections —
    numerically identical to the Gluon ``_LlamaLayer`` (the parity
    test drives both from one checkpoint).
  * :func:`train_step` runs one fused 1F1B fine-tune step whose loss
    is ``chunked_softmax_ce`` — the (N, V) logits are never
    materialized even under pipeline + tensor parallelism.
  * :func:`save_llama_stacked` reshards the trained params back to an
    HF sharded checkpoint (inverse RoPE permutation included) that
    ``load_hf_llama`` / HF tooling can read.

Reference analog: upstream's closest is the manual model-parallel
example (SURVEY.md §2.3 "Model/tensor parallel") — checkpoint-to-mesh
streaming has no reference ancestor; designed TPU-first.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .hf_loader import (_permute_qk, _rope_perm, _shard_paths,
                        read_safetensors, write_safetensors_sharded)

__all__ = ["load_llama_stacked", "make_stage_fn", "make_chunked_loss",
           "forward_logits", "train_step", "save_llama_stacked"]

# layer-param short name -> (HF suffix, sharding kind)
# kinds: col = output-dim tp shard, row = input-dim tp shard,
# norm = replicated gamma
_LAYER_TABLE = {
    "q": ("self_attn.q_proj.weight", "col"),
    "k": ("self_attn.k_proj.weight", "col"),
    "v": ("self_attn.v_proj.weight", "col"),
    "o": ("self_attn.o_proj.weight", "row"),
    "gate": ("mlp.gate_proj.weight", "col"),
    "up": ("mlp.up_proj.weight", "col"),
    "down": ("mlp.down_proj.weight", "row"),
    "innorm": ("input_layernorm.weight", "norm"),
    "postnorm": ("post_attention_layernorm.weight", "norm"),
}


def _open_views(path):
    """Every tensor in the (possibly sharded) checkpoint as a lazy
    mmap view; nothing is copied until a shard callback slices."""
    views = {}
    for shard in _shard_paths(path):
        views.update(read_safetensors(shard))
    return views


def _stacked_specs(tp_axis, pp_axis):
    """Leaf layout is (pp_stages, layers_per_stage, *tensor_dims):
    the stage dim shards over pp (the pipeline contract), the
    within-stage layer dim stays local, tp shards the Megatron dim."""
    from jax.sharding import PartitionSpec as P
    out = {}
    for name, (_, kind) in _LAYER_TABLE.items():
        if kind == "col":
            out[name] = P(pp_axis, None, tp_axis, None)
        elif kind == "row":
            out[name] = P(pp_axis, None, None, tp_axis)
        else:
            out[name] = P(pp_axis, None, None)
    return out


def load_llama_stacked(path, mesh, num_heads, num_kv_heads,
                       rope_base=10000.0, *, tp_axis="tp",
                       pp_axis="pp", dtype=np.float32):
    """Stream an HF Llama checkpoint onto a ``(tp, pp)`` mesh.

    Returns ``(params, specs, config)``:

    * ``params["layers"]`` — dict of STACKED
      ``(pp_stages, layers_per_stage, ...)`` jax arrays: the stage axis
      is sharded over ``pp_axis``, the within-stage layer axis is local,
      and Megatron col/row sharding rides ``tp_axis``; each device shard
      is built by ``jax.make_array_from_callback`` reading ONLY its own
      byte range from the checkpoint mmap (q/k rows pass through the
      rotate-half → adjacent-pair RoPE permutation lazily, per shard).
      Global layer id = stage * layers_per_stage + local index.
    * ``params["embed"]``, ``params["final_norm"]``, ``params["head"]``
      — replicated (``head`` is None for tied checkpoints; use the
      embedding).
    * ``specs`` — the PartitionSpec pytree for ``params["layers"]``
      (feed to ``pipeline_value_and_grad(param_specs=...)``).
    * ``config`` — dict(num_layers, layers_per_stage, units, hidden,
      vocab, head_dim, num_heads, num_kv_heads, rope_base) inferred
      from shapes.

    Requires ``mesh.shape[pp_axis]`` to DIVIDE ``num_layers`` (each
    stage runs ``num_layers / pp`` consecutive decoder layers — the
    homogeneous-stage pipeline contract) and ``tp | num_kv_heads``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    views = _open_views(path)
    if "model.embed_tokens.weight" not in views:
        raise MXNetError(f"{path}: not a Llama checkpoint "
                         "(model.embed_tokens.weight missing)")
    vocab, units = views["model.embed_tokens.weight"].shape
    n_layers = 0
    while f"model.layers.{n_layers}.self_attn.q_proj.weight" in views:
        n_layers += 1
    if not n_layers:
        raise MXNetError(f"{path}: no decoder layers found")
    hidden = views["model.layers.0.mlp.gate_proj.weight"].shape[0]
    d = units // num_heads
    kv_rows = views["model.layers.0.self_attn.k_proj.weight"].shape[0]
    if kv_rows != num_kv_heads * d:
        raise MXNetError(
            f"k_proj rows {kv_rows} != num_kv_heads*head_dim "
            f"{num_kv_heads}*{d} — wrong num_heads/num_kv_heads?")
    tp = mesh.shape[tp_axis]
    pp = mesh.shape[pp_axis]
    if n_layers % pp:
        raise MXNetError(
            f"num_layers={n_layers} not divisible by mesh "
            f"{pp_axis}={pp} (stages must hold equal layer blocks)")
    lpp = n_layers // pp
    for what, val in (("num_heads", num_heads),
                      ("num_kv_heads", num_kv_heads),
                      ("hidden", hidden)):
        if val % tp:
            raise MXNetError(f"{what}={val} not divisible by "
                             f"{tp_axis}={tp}")

    # full-tensor row permutations for the RoPE layout change; slicing
    # perm[rows] keeps the per-shard read lazy
    perms = {"q": np.concatenate(
        [h * d + _rope_perm(d) for h in range(num_heads)]),
        "k": np.concatenate(
        [h * d + _rope_perm(d) for h in range(num_kv_heads)])}

    specs = _stacked_specs(tp_axis, pp_axis)
    layers = {}
    for name, (suffix, kind) in _LAYER_TABLE.items():
        per_layer = [views[f"model.layers.{i}.{suffix}"]
                     for i in range(n_layers)]
        # (pp, layers_per_stage, *tensor): global layer id is
        # stage * lpp + j — stage blocks are contiguous layer runs,
        # the GPipe assignment parallel.planning._layer_stage uses
        shape = (pp, lpp) + per_layer[0].shape
        sharding = NamedSharding(mesh, specs[name])
        perm = perms.get(name)

        def cb(index, per_layer=per_layer, perm=perm):
            ss, js = index[0], index[1]
            rest = index[2:]
            stages = []
            for stg in range(ss.start or 0,
                             ss.stop if ss.stop is not None else pp):
                slabs = []
                for j in range(js.start or 0,
                               js.stop if js.stop is not None
                               else lpp):
                    v = per_layer[stg * lpp + j]
                    if perm is not None:
                        rows = perm[rest[0]]
                        slab = v[rows]
                        if len(rest) > 1:
                            slab = slab[(slice(None),)
                                        + tuple(rest[1:])]
                    else:
                        slab = v[tuple(rest)]
                    slabs.append(np.asarray(slab, dtype))
                stages.append(np.stack(slabs))
            return np.stack(stages)

        layers[name] = jax.make_array_from_callback(shape, sharding,
                                                    cb)

    repl = NamedSharding(mesh, P())
    embed = jax.device_put(
        np.asarray(views["model.embed_tokens.weight"], dtype), repl)
    final_norm = jax.device_put(
        np.asarray(views["model.norm.weight"], dtype), repl)
    head = None
    if "lm_head.weight" in views:
        head = jax.device_put(
            np.asarray(views["lm_head.weight"], dtype), repl)
    params = {"layers": layers, "embed": embed,
              "final_norm": final_norm, "head": head}
    config = dict(num_layers=n_layers, layers_per_stage=lpp,
                  units=units, hidden=hidden,
                  vocab=vocab, head_dim=d, num_heads=num_heads,
                  num_kv_heads=num_kv_heads, rope_base=rope_base)
    return params, specs, config


def _rms(x, gamma, eps):
    import jax.numpy as jnp
    from jax import lax
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * gamma


def make_stage_fn(config, tp_axis="tp", eps=1e-5):
    """Functional decoder STAGE for the pipeline: a block of
    ``layers_per_stage`` decoder layers, each matching the Gluon
    ``_LlamaLayer`` math exactly (RMSNorm eps 1e-5, adjacent-pair
    RoPE, GQA SDPA, SwiGLU), with Megatron tp: q/k/v/gate/up consume
    their column shard locally (heads split over tp — GQA groups stay
    aligned because ``tp | num_kv_heads``), o/down row-parallel
    partials closed by ONE ``lax.psum`` each.  Stage leaves arrive as
    ``(layers_per_stage, ...)`` local blocks (the pipeline strips the
    pp-sharded stage dim); the layer loop is unrolled — XLA sees a
    static chain, the TPU-friendly form."""
    h, kv, d = (config["num_heads"], config["num_kv_heads"],
                config["head_dim"])
    base = config["rope_base"]

    # NB: the returned closure must capture only scalars and
    # module-level functions — pipeline._capture_key keys opaque
    # objects by id, so a per-call inner function would defeat the
    # pipeline executable cache and recompile every step.
    def stage(local, x):
        # layers_per_stage derived from the leaves themselves: a
        # config/array mismatch is then impossible
        lpp = next(iter(local.values())).shape[0]
        for j in range(lpp):
            x = _decoder_layer({k: v[j] for k, v in local.items()},
                               x, h, kv, d, base, eps, tp_axis)
        return x

    return stage


def _decoder_layer(lp, x, h, kv, d, base, eps, tp_axis):
    """One decoder layer on its local tp shards (module-level so the
    pipeline executable cache keys it stably)."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops.attention import dot_product_attention, rope

    from jax.lax import axis_size
    tp = axis_size(tp_axis) if tp_axis else 1
    b, s = x.shape[0], x.shape[1]
    hl, kvl = h // tp, kv // tp
    hx = _rms(x, lp["innorm"], eps)
    q = rope(jnp.dot(hx, lp["q"].T).reshape(b, s, hl, d), base=base)
    k = rope(jnp.dot(hx, lp["k"].T).reshape(b, s, kvl, d), base=base)
    v = jnp.dot(hx, lp["v"].T).reshape(b, s, kvl, d)
    att = dot_product_attention(q, k, v, causal=True)
    o_part = jnp.dot(att.reshape(b, s, hl * d), lp["o"].T)
    if tp_axis:
        o_part = lax.psum(o_part, tp_axis)
    x = x + o_part
    hx = _rms(x, lp["postnorm"], eps)
    gate = jnp.dot(hx, lp["gate"].T)
    up = jnp.dot(hx, lp["up"].T)
    dn = jnp.dot(_silu(gate) * up, lp["down"].T)
    if tp_axis:
        dn = lax.psum(dn, tp_axis)
    return x + dn


def _silu(x):
    import jax
    return jax.nn.silu(x)


def make_chunked_loss(params, config, tp_axis="tp", vocab_chunk=None,
                      eps=1e-5):
    """Pipeline ``loss_fn``: final RMSNorm + streaming large-vocab CE
    over next-token labels — the (N, V) logits tensor is never
    materialized (``chunked_softmax_ce``'s scan), composing with both
    pipeline and tensor parallelism.  Head/embedding stay frozen (the
    embeddings-frozen fine-tune mode); returns the microbatch-mean
    loss, ``lax.pmean``-ed over ``tp`` (replicated activations make it
    identical per shard — the pmean keeps shard_map's varying-axes
    accounting exact)."""
    import jax.numpy as jnp
    from jax import lax

    from ..ops.nn import chunked_softmax_ce

    head_w = params["head"] if params["head"] is not None \
        else params["embed"]
    gamma = params["final_norm"]
    chunk = int(vocab_chunk or max(64, config["vocab"] // 4))
    u = config["units"]

    def loss_fn(out_mb, y_mb):
        hid = _rms(out_mb, gamma, eps)
        pred = hid[:, :-1].reshape(-1, u)
        labels = y_mb[:, 1:].reshape(-1).astype(jnp.int32)
        per_row = chunked_softmax_ce(pred, head_w, labels, chunk=chunk)
        loss = per_row.mean()
        if tp_axis:
            loss = lax.pmean(loss, tp_axis)
        return loss

    return loss_fn


def forward_logits(params, tokens, config, mesh, specs, *,
                   tp_axis="tp", pp_axis="pp", n_microbatches=None,
                   eps=1e-5):
    """Full forward to logits through the GPipe pipeline (parity /
    eval path; training uses :func:`train_step`)."""
    import jax.numpy as jnp

    from ..parallel.pipeline import pipeline_apply

    m = n_microbatches or mesh.shape[pp_axis]
    x = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)]
    stage = make_stage_fn(config, tp_axis=tp_axis, eps=eps)
    hid = pipeline_apply(stage, params["layers"], x, m, mesh=mesh,
                         axis=pp_axis, param_specs=specs)
    hid = _rms(hid, params["final_norm"], eps)
    head_w = params["head"] if params["head"] is not None \
        else params["embed"]
    return jnp.dot(hid, jnp.asarray(head_w).T)


def train_step(params, tokens, config, mesh, specs, *, lr=1e-2,
               tp_axis="tp", pp_axis="pp", n_microbatches=None,
               vocab_chunk=None, eps=1e-5):
    """ONE fused 1F1B fine-tune step: embed (frozen) → pipelined
    decoder stack (trained, tp×pp sharded) → chunked CE (frozen head).
    Returns ``(loss, params)`` with layer params SGD-updated in their
    sharded stacked layout (update arithmetic preserves shardings)."""
    import jax

    from ..parallel.pipeline import pipeline_value_and_grad

    m = n_microbatches or mesh.shape[pp_axis]
    import jax.numpy as jnp
    x = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)]
    stage = make_stage_fn(config, tp_axis=tp_axis, eps=eps)
    loss_fn = make_chunked_loss(params, config, tp_axis=tp_axis,
                                vocab_chunk=vocab_chunk, eps=eps)
    # tp is closed by psums (row-parallel projections + chunked CE):
    # declare it so replicated leaves (norm weights) get true
    # replicated grads back, not per-device partials
    loss, grads = pipeline_value_and_grad(
        stage, params["layers"], x, jnp.asarray(tokens, jnp.int32),
        loss_fn, m, mesh=mesh, axis=pp_axis, param_specs=specs,
        grad_reduce_axes=(tp_axis,))
    new_layers = jax.tree_util.tree_map(
        lambda p, g: p - lr * g, params["layers"], grads)
    return loss, {**params, "layers": new_layers}


def save_llama_stacked(params, dir_path, config, max_shard_bytes,
                       dtype=np.float32, metadata=None):
    """Reshard the (possibly trained) stacked params back to an
    HF-layout sharded checkpoint readable by ``load_hf_llama`` and HF
    tooling (inverse RoPE row permutation applied to q/k).

    Uses :func:`write_safetensors_sharded`'s streaming form: each
    tensor is gathered from its device shards only while ITS shard
    file is being written and dropped right after — peak host memory
    is one shard file, not the model (the save-side mirror of
    :func:`load_llama_stacked`'s contract)."""
    h, kv, d = (config["num_heads"], config["num_kv_heads"],
                config["head_dim"])
    # layers_per_stage derived from the arrays (not config) so a
    # hand-built or stale config cannot silently mis-index layers
    lpp = next(iter(params["layers"].values())).shape[1]
    sources = {}                      # hf name -> (kind, array, layer)
    for name, (suffix, _) in _LAYER_TABLE.items():
        for i in range(config["num_layers"]):
            sources[f"model.layers.{i}.{suffix}"] = (
                name, params["layers"][name], i)
    sources["model.embed_tokens.weight"] = (None, params["embed"], None)
    sources["model.norm.weight"] = (None, params["final_norm"], None)
    if params["head"] is not None:
        sources["lm_head.weight"] = (None, params["head"], None)

    def shape_of(kind, arr, layer):
        return tuple(arr.shape[2:] if layer is not None else arr.shape)

    specs = {nm: (shape_of(*src), dtype)
             for nm, src in sources.items()}

    def materialize(nm):
        kind, arr, layer = sources[nm]
        # stacked layout is (stage, layer_in_stage, ...): global layer
        # i lives at [i // lpp, i % lpp]
        a = np.asarray(arr[layer // lpp, layer % lpp]
                       if layer is not None else arr, dtype)
        if kind == "q":
            a = _permute_qk(a, h, d, invert=True).astype(dtype)
        elif kind == "k":
            a = _permute_qk(a, kv, d, invert=True).astype(dtype)
        return a

    return write_safetensors_sharded(
        dir_path, specs, max_shard_bytes, materialize=materialize,
        metadata=metadata or {"format": "pt",
                              "producer": "mxnet_tpu.llama_spmd"})
