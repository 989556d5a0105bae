"""A decoder LM behind ``serving.Server``, through the program's normal
entry points: ``get_llama(preset, ...)`` -> ``LlamaForCausalLM`` ->
``Server(net, buckets=..., max_new_tokens=..., cache_dtype=...)``, as
``chip_smoke.py``'s serve phase builds them — but with the weights made
ON THE DEVICE in one jitted call from the seed, in the type they are
served in, and installed through the parameters' load path
(``Parameter._load_init``, what ``load_parameters`` uses), instead of
34.6 s of host numpy (smoke timing, PR 24)."""
import math

import numpy as np


def _make_weights(shapes_dtypes, seed, device):
    """Every parameter in one program: matrices N(0, 2 / (fan_in +
    fan_out)) (Xavier's variance), vectors (norm gains) ones."""
    import jax
    import jax.numpy as jnp

    def make(key):
        out = []
        for i, (shape, dtype) in enumerate(shapes_dtypes):
            if len(shape) < 2:
                out.append(jnp.ones(shape, dtype))
                continue
            std = math.sqrt(2.0 / (shape[0] + shape[1]))
            out.append((std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype))
        return out

    key = jax.device_put(jax.random.PRNGKey(seed), device)
    return jax.jit(make)(key)


def build_server(shapes, seed, device, max_queue):
    """(net, server, ctx).  ``shapes`` is the configuration file's
    content, or its ``rehearsal`` group."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import LlamaForCausalLM, get_llama
    from mxnet_tpu.serving import Server

    prog, serving = shapes["program"], shapes["serving"]
    ctx = mx.Context(device.platform, 0)
    mx.random.seed(seed % (2 ** 31 - 1))
    net = LlamaForCausalLM(
        get_llama(prog["preset"], vocab_size=int(shapes["vocab_size"]),
                  # the file's sizes are what runs, whatever the preset holds
                  units=int(shapes["hidden_size"]),
                  hidden=int(shapes["intermediate_size"]),
                  num_layers=int(shapes["num_hidden_layers"]),
                  num_heads=int(shapes["num_attention_heads"]),
                  num_kv_heads=int(shapes["num_key_value_heads"]),
                  rope_base=float(shapes["rope_theta"]),
                  sliding_window=shapes["sliding_window"]),
        tie_embeddings=bool(shapes["tie_word_embeddings"]))
    net.cast(serving["weight_dtype"])
    params = list(net.collect_params().values())
    for p in params:
        p.grad_req = "null"
    values = _make_weights(
        [(tuple(p.shape), serving["weight_dtype"]) for p in params],
        seed % (2 ** 31 - 1), device)
    for p, v in zip(params, values):
        p._load_init(nd.NDArray(v, ctx=ctx), ctx=ctx)
    srv = Server(net, buckets=[tuple(b) for b in serving["buckets"]],
                 max_new_tokens=int(serving["max_new_tokens"]), ctx=ctx,
                 cache_dtype=serving["cache_dtype"], max_queue=max_queue)
    return net, srv, ctx


def n_params(net):
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values())


def full_forward_logits(net, tokens, ctx):
    """The plain reference ``correct`` holds a served request to: one
    full-sequence forward of the same weights, no cache, no buckets."""
    from mxnet_tpu import nd
    return net(nd.array(tokens[None, :], ctx=ctx)).asnumpy()[0] \
        .astype(np.float32)
