"""Llama-family decoder-only LMs (BASELINE config #5 "Llama-3-8B via
Gluon Blocks" — SURVEY.md §2.6 "External zoos" stretch target).

TPU-first design:

* RMSNorm / RoPE / fused SDPA are single registered ops (XLA fuses the
  rest); attention takes the flash path on chip, and the whole
  next-token-prediction step hybridizes to one XLA program.
* **Grouped-query attention**: ``num_kv_heads < num_heads`` shrinks the
  KV projections (Llama-3's layout); KV heads are broadcast to query
  heads inside the compiled graph.
* **Long context is first-class**: ``attn_impl="ring"`` routes
  attention through the SPMD ring-attention kernel over a
  sequence-parallel mesh axis (``sp``), so sequences shard across
  devices (SURVEY §5 long-context row).
* ``llama3_8b()`` builds the real 8B geometry — on a single v5e it is
  for sharded meshes/dryruns; ``llama_tiny`` trains in tests.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..profiler import device_scope

__all__ = ["LlamaModel", "LlamaForCausalLM", "RMSNormBlock",
           "get_llama", "llama_tiny", "llama3_8b"]


class RMSNormBlock(HybridBlock):
    """RMSNorm with learned gamma (Llama's norm; op: ``RMSNorm``)."""

    def __init__(self, units, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma=None):
        return F.RMSNorm(x, gamma, eps=self._eps)


class _LlamaAttention(HybridBlock):
    def __init__(self, units, num_heads, num_kv_heads, rope_base,
                 attn_impl="sdpa", sp_axis="sp", sliding_window=None,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} % num_heads {num_heads}")
        if num_heads % num_kv_heads:
            raise MXNetError("num_heads must be a multiple of "
                             "num_kv_heads (GQA groups)")
        if sliding_window is not None and attn_impl == "ring":
            raise MXNetError(
                "sliding_window with attn_impl='ring' is not "
                "supported: the band already caps per-query compute "
                "at O(W) — use the sdpa/flash path, or ring WITHOUT "
                "a window for full-causal sequence parallelism")
        self._h = num_heads
        self._kv = num_kv_heads
        self._d = units // num_heads
        self._base = rope_base
        self._impl = attn_impl
        self._sp_axis = sp_axis
        self._window = sliding_window
        #: the device scope of this mixer and of what the layer and the
        #: model do on its behalf (its norm, its residual add, its mask)
        self.scope = "mxtpu.mixer.swa" if sliding_window is not None \
            else "mxtpu.mixer.full"
        with self.name_scope():
            self.q_proj = nn.Dense(num_heads * self._d, flatten=False,
                                   use_bias=False, in_units=units,
                                   prefix="q_")
            self.k_proj = nn.Dense(num_kv_heads * self._d, flatten=False,
                                   use_bias=False, in_units=units,
                                   prefix="k_")
            self.v_proj = nn.Dense(num_kv_heads * self._d, flatten=False,
                                   use_bias=False, in_units=units,
                                   prefix="v_")
            self.o_proj = nn.Dense(units, flatten=False, use_bias=False,
                                   in_units=num_heads * self._d,
                                   prefix="o_")

    def prefill(self, x, cache_k, cache_v, perm=None):
        """Batched prompt pass: full-sequence causal attention that
        also writes K/V for every prompt position into the caches —
        one program instead of S sequential steps.

        A cache SHORTER than the prompt is the rolling (sliding-
        window) buffer: slot j must hold the newest absolute position
        p ≡ j (mod C), so the prompt TAIL is written through the
        ``perm`` slot permutation (built ONCE by the caller — it
        depends only on (S, C), not the layer)."""
        from .. import ndarray as nd
        b, s = x.shape[0], x.shape[1]
        h, kv, d = self._h, self._kv, self._d
        with device_scope(self.scope):
            q = nd.rope(self.q_proj(x).reshape((b, s, h, d)),
                        base=self._base)
            k = nd.rope(self.k_proj(x).reshape((b, s, kv, d)),
                        base=self._base)
            v = self.v_proj(x).reshape((b, s, kv, d))
            if perm is None:
                nd._cache_update(cache_k, k, offset=0, out=cache_k)
                nd._cache_update(cache_v, v, offset=0, out=cache_v)
            else:
                nd._cache_update(cache_k, nd.take(k, perm, axis=1),
                                 offset=0, out=cache_k)
                nd._cache_update(cache_v, nd.take(v, perm, axis=1),
                                 offset=0, out=cache_v)
            out = nd.dot_product_attention(q, k, v, causal=True,
                                           window=self._window)
            return self.o_proj(out.reshape((b, s, h * d)))

    def step(self, x, cache_k, cache_v, offset, mask, slot=None):
        """Incremental decode: x (B, 1, units), caches
        (B, C, KV, D) written in place; ``mask`` is the shared
        key-validity mask built once per decode_step.  ``offset`` is
        the ABSOLUTE position (drives RoPE); ``slot`` is the cache
        write index — ``offset % C`` for a rolling sliding-window
        buffer, defaulting to ``offset`` for the classic cache."""
        from .. import ndarray as nd
        b = x.shape[0]
        h, kv, d = self._h, self._kv, self._d
        with device_scope(self.scope):
            q = nd.rope(self.q_proj(x).reshape((b, 1, h, d)),
                        offset=offset, base=self._base)
            k_t = nd.rope(self.k_proj(x).reshape((b, 1, kv, d)),
                          offset=offset, base=self._base)
            v_t = self.v_proj(x).reshape((b, 1, kv, d))
            # dynamic-offset scatter: one compiled program for every step
            if slot is None:
                slot = offset
            nd._cache_update(cache_k, k_t, offset=slot, out=cache_k)
            nd._cache_update(cache_v, v_t, offset=slot, out=cache_v)
            # GQA is native in dot_product_attention: the unrepeated
            # cache is attended directly (no (B, max_len, H, D)
            # materialization)
            out = nd.dot_product_attention(q, cache_k, cache_v, mask,
                                           use_mask=True)
            return self.o_proj(out.reshape((b, 1, h * d)))

    def hybrid_forward(self, F, x):
        b, s = x.shape[0], x.shape[1]
        h, kv, d = self._h, self._kv, self._d
        with device_scope(self.scope):
            q = F.rope(self.q_proj(x).reshape((b, s, h, d)),
                       base=self._base)
            k = F.rope(self.k_proj(x).reshape((b, s, kv, d)),
                       base=self._base)
            v = self.v_proj(x).reshape((b, s, kv, d))
            if self._impl == "ring":
                # the ring kernel groups query heads per KV head
                # internally, so only the small KV tensors travel the
                # ICI ring
                from ..parallel.ring_attention import \
                    ring_attention_sharded
                out = ring_attention_sharded(q, k, v, axis=self._sp_axis,
                                             causal=True)
            else:
                # GQA is native in the attention op (grouped einsum)
                out = F.dot_product_attention(q, k, v, causal=True,
                                              window=self._window)
            return self.o_proj(out.reshape((b, s, h * d)))


class _LlamaMLP(HybridBlock):
    """SwiGLU feed-forward: down(silu(gate(x)) * up(x))."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_proj = nn.Dense(hidden, flatten=False,
                                      use_bias=False, in_units=units,
                                      prefix="gate_")
            self.up_proj = nn.Dense(hidden, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="up_")
            self.down_proj = nn.Dense(units, flatten=False,
                                      use_bias=False, in_units=hidden,
                                      prefix="down_")

    def hybrid_forward(self, F, x):
        with device_scope("mxtpu.mlp"):
            return self.down_proj(F.silu(self.gate_proj(x))
                                  * self.up_proj(x))


class _LlamaLayer(HybridBlock):
    def __init__(self, units, hidden, num_heads, num_kv_heads,
                 rope_base, attn_impl, sp_axis="sp",
                 sliding_window=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNormBlock(units, prefix="innorm_")
            self.attn = _LlamaAttention(units, num_heads, num_kv_heads,
                                        rope_base, attn_impl,
                                        sp_axis=sp_axis,
                                        sliding_window=sliding_window,
                                        prefix="attn_")
            self.post_norm = RMSNormBlock(units, prefix="postnorm_")
            self.mlp = _LlamaMLP(units, hidden, prefix="mlp_")

    # a sub-layer's norm and residual add carry its device scope

    def _ffn(self, x):
        with device_scope("mxtpu.mlp"):
            return x + self.mlp(self.post_norm(x))

    def hybrid_forward(self, F, x):
        with device_scope(self.attn.scope):
            x = x + self.attn(self.input_norm(x))
        return self._ffn(x)

    def prefill(self, x, cache_k, cache_v, perm=None):
        with device_scope(self.attn.scope):
            x = x + self.attn.prefill(self.input_norm(x), cache_k,
                                      cache_v, perm=perm)
        return self._ffn(x)

    def step(self, x, cache_k, cache_v, offset, mask, slot=None):
        with device_scope(self.attn.scope):
            x = x + self.attn.step(self.input_norm(x), cache_k, cache_v,
                                   offset, mask, slot=slot)
        return self._ffn(x)


class LlamaModel(HybridBlock):
    def __init__(self, vocab_size, units, hidden, num_layers, num_heads,
                 num_kv_heads=None, rope_base=10000.0,
                 attn_impl="sdpa", sp_axis="sp", sliding_window=None,
                 **kwargs):
        super().__init__(**kwargs)
        num_kv_heads = num_kv_heads or num_heads
        self._units = units
        self.vocab_size = vocab_size
        self.sliding_window = sliding_window
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units,
                                      prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = _LlamaLayer(units, hidden, num_heads,
                                    num_kv_heads, rope_base, attn_impl,
                                    sp_axis=sp_axis,
                                    sliding_window=sliding_window,
                                    prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.final_norm = RMSNormBlock(units, prefix="finalnorm_")

    def embed_tokens(self, tokens):
        with device_scope("mxtpu.embed"):
            return self.embed(tokens)

    def hybrid_forward(self, F, tokens):
        x = self.embed_tokens(tokens)
        for layer in self.layers:
            x = layer(x)
        with device_scope("mxtpu.head"):
            return self.final_norm(x)


class LlamaForCausalLM(HybridBlock):
    """LM head over LlamaModel.

    ``tie_embeddings=True`` (default) shares the embedding matrix with
    the head — the Llama-3.2-1B/3B layout.  Llama-3-8B/70B use an
    UNTIED head: pass ``tie_embeddings=False`` with ``llama3_8b()``
    (that separate head adds ~0.53B params on top of the model's
    7.50B)."""

    def __init__(self, model: LlamaModel, tie_embeddings=True,
                 **kwargs):
        super().__init__(**kwargs)
        self._tied = tie_embeddings
        with self.name_scope():
            self.model = model
            if not tie_embeddings:
                self.lm_head = nn.Dense(model.vocab_size, flatten=False,
                                        use_bias=False,
                                        in_units=model._units,
                                        prefix="head_")

    def _head_weight(self, ctx):
        """The (V, U) LM-head matrix — the tied embedding or the
        untied head's Dense weight (one place for the branch: shared by
        hybrid_forward, _head, and the chunked loss)."""
        return (self.model.embed.weight.data(ctx) if self._tied
                else self.lm_head.weight.data(ctx))

    def hybrid_forward(self, F, tokens):
        h = self.model(tokens)
        with device_scope("mxtpu.head"):
            if self._tied:
                w = self._head_weight(h.context)
                b, s, u = h.shape
                return F.dot(h.reshape((b * s, u)), w,
                             transpose_b=True).reshape(
                                 (b, s, self.model.vocab_size))
            return self.lm_head(h)

    @staticmethod
    def _check_cache_dtype(dtype):
        """KV caches must be FLOAT: an integer cache dtype truncates
        every K/V write via _cache_update's cast-on-store (the
        historical int32-leak bug) and generates garbage silently."""
        import jax.numpy as jnp
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            raise MXNetError(
                f"KV cache dtype must be floating, got {dtype!r} "
                "(an int cache truncates every K/V write)")

    def _rolling_cache_len(self, max_len, rolling):
        """Cache length for (max_len, rolling) — ONE place for the
        rolling policy, shared by init_cache and generate_fused."""
        if not rolling:
            return max_len
        w = self.model.sliding_window
        if w is None:
            raise MXNetError(
                "rolling=True requires a model with sliding_window "
                "set (Mistral-style)")
        return min(int(w), max_len)

    def init_cache(self, batch_size, max_len, ctx=None, rolling=False,
                   dtype="float32"):
        """Preallocate the KV caches (B, C, KV, D): one flat list ``[k0,
        v0, k1, v1, ...]``, the order of ``state_spec``.

        ``rolling=True`` (sliding-window models only) allocates the
        Mistral rolling buffer: C = min(sliding_window, max_len), so
        decode memory is O(W) regardless of generation length —
        positions wrap via ``offset % C`` and out-of-window entries
        are overwritten exactly when they leave the band.

        ``dtype="bfloat16"`` halves cache HBM (and decode-time cache
        bandwidth — the dominant traffic at batch 1): K/V writes cast
        on store, attention math still accumulates f32 (mixed-dtype
        dots promote)."""
        from .. import ndarray as nd
        self._check_cache_dtype(dtype)
        cache_len = self._rolling_cache_len(max_len, rolling)
        return [nd.zeros(shape, ctx=ctx, dtype=dt) for _n, _k, shape, dt
                in self.state_spec(batch_size, cache_len, dtype)]

    def state_spec(self, slots, cache_len, dtype="float32"):
        """What the serving plane's pool holds for this model
        (docs/serving.md, "State kinds"): one K and one V page a layer,
        ``(name, kind, shape, dtype)`` rows in the flat order ``[k0, v0,
        k1, v1, ...]`` that ``prefill`` / ``decode_step`` take.
        A sliding-window model's pages are full length (the band is a
        mask), so the kind only says which mask reads them."""
        self._check_cache_dtype(dtype)
        kind = "kv_full" if self.model.sliding_window is None \
            else "kv_window"
        rows = []
        for i, layer in enumerate(self.model.layers):
            shp = (slots, cache_len, layer.attn._kv, layer.attn._d)
            rows += [(f"layer{i}_k", kind, shp, str(dtype)),
                     (f"layer{i}_v", kind, shp, str(dtype))]
        return rows

    def _layer_caches(self, caches):
        """(layer, K page, V page) over the flat ``state_spec`` list."""
        return zip(self.model.layers, caches[0::2], caches[1::2])

    def _head(self, h):
        """LM-head projection shared by full-forward and decode paths."""
        from .. import ndarray as nd
        with device_scope("mxtpu.head"):
            if self._tied:
                return nd.dot(h.reshape((-1, self.model._units)),
                              self._head_weight(h.context),
                              transpose_b=True)
            return self.lm_head(h).reshape((-1, self.model.vocab_size))

    def prefill(self, tokens, caches, last_pos=None):
        """Batched prompt pass filling the caches; returns the LAST
        position's logits (B, vocab).

        ``last_pos`` (an NDArray of per-row indices, shape (B,)) reads
        the logits at each row's OWN last real token instead of the
        final position — the right-padded bucket-prompt shape the
        serving plane feeds (pad rows beyond ``last_pos`` stay causal
        garbage that the decode-time validity mask never exposes: true
        of K/V pages only; a model with recurrent state must stop at
        ``last_pos``, as ``models/sambay.py`` does)."""
        import numpy as np
        from .. import ndarray as nd
        x = self.model.embed_tokens(tokens)
        s = tokens.shape[1]
        c = caches[0].shape[1]
        perm = None
        if s > c:
            # rolling buffer shorter than the prompt: slot j holds the
            # newest position p ≡ j (mod C); one permutation for ALL
            # layers (it depends only on (S, C))
            start = s - c
            perm = nd.array(
                (start + (np.arange(c) - start) % c).astype("f4"),
                ctx=tokens.context)
        for layer, ck, cv in self._layer_caches(caches):
            x = layer.prefill(x, ck, cv, perm=perm)
        with device_scope("mxtpu.head"):
            h = self.model.final_norm(x)
            if last_pos is None:
                return self._head(h[:, -1:])
            b = tokens.shape[0]
            # per-row gather as a one-hot contraction (hybridizable: no
            # host-side indices, positions ride as a dynamic input)
            pos = nd.arange(s, ctx=tokens.context).reshape((1, s))
            lp = last_pos.reshape((-1, 1))
            onehot = (pos <= lp) * (pos >= lp)             # (B, S) {0,1}
            sel = (h * onehot.reshape((b, s, 1))).sum(axis=1)
            return self._head(sel.reshape((b, 1, self.model._units)))

    def decode_step(self, token, caches, offset):
        """One incremental step: token (B, 1) → logits (B, vocab).

        ``offset`` may be a python number / 0-d NDArray (one shared
        position — the classic generation loop) or a (B,)-shaped
        NDArray giving every batch row its OWN absolute position (the
        continuous-batching serving shape: each slot decodes at its own
        depth; rope, the cache scatter, and the validity mask all
        specialize per row through the same dynamic-input path, so the
        mixed-depth batch still reuses ONE compiled program)."""
        from .. import ndarray as nd
        x = self.model.embed_tokens(token)
        # key-validity mask (pos <= offset), shared across all layers;
        # offset rides the dynamic-scalar path (nd.full would bake it
        # into static attrs and compile a fresh program per step)
        max_len = caches[0].shape[1]
        # build the mask on the token's device: the default ctx is
        # cpu(0), and a host mask would not mix with chip arrays in one
        # program.  offset may be a python number (the
        # per-step path) or a 0-d NDArray (the fused on-device
        # generation loop carries it through lax.scan).
        off = offset if isinstance(offset, nd.NDArray) else float(offset)
        w = self.model.sliding_window
        with device_scope(self.model.layers[0].attn.scope):
            pos = nd.arange(max_len, ctx=token.context)
            if isinstance(off, nd.NDArray) and off.ndim == 1:
                slot, mask = self._slot_masks(x.shape[0], off, pos, w,
                                              max_len)
            else:
                slot, mask = self._shared_mask(off, pos, w, max_len)
        for layer, ck, cv in self._layer_caches(caches):
            x = layer.step(x, ck, cv, offset, mask, slot=slot)
        with device_scope("mxtpu.head"):
            return self._head(self.model.final_norm(x))

    @staticmethod
    def _shared_mask(off, pos, w, max_len):
        """(cache write slot or None, key-validity mask) for ONE
        position every row shares."""
        slot = None
        if w is not None and max_len <= int(w):
            # ROLLING buffer (cache holds exactly the window): slot
            # j's absolute position is off - ((off - j) mod C), always
            # inside (off-C, off] — every WRITTEN slot is valid.
            # Validity is just "written": j <= off, or everything once
            # the buffer has wrapped (off >= C).
            c = float(max_len)
            slot = off % c
            # validity is just "slot written yet": pos <= off covers
            # both regimes — after the buffer wraps (off >= c) it is
            # all-true, which is exactly right (every slot then holds
            # a position inside the window)
            mask = pos <= off
        else:
            mask = pos <= off
            if w is not None:
                # classic full cache + sliding window: only the last W
                # entries are live — (off-W, off], same band the
                # prefill kernels apply
                mask = mask * (pos > off - float(w))
        return slot, mask.reshape((1, 1, 1, max_len))

    @staticmethod
    def _slot_masks(b, off, pos, w, max_len):
        """The per-slot form: ``off`` is (B,) absolute positions.
        Same math as the shared-offset path, with the mask, rope
        offsets, and cache-scatter slots specialized PER ROW (rope and
        ``_cache_update`` broadcast a (B,)-shaped dynamic offset).
        Rows are independent in attention, so one slot's cache garbage
        (an evicted request) can never reach another's logits."""
        posr = pos.reshape((1, max_len))
        offv = off.reshape((-1, 1))
        slot = None
        if w is not None and max_len <= int(w):
            # rolling buffer: identical policy to the shared path,
            # elementwise over slots
            slot = off % float(max_len)
            mask = posr <= offv
        else:
            mask = posr <= offv
            if w is not None:
                mask = mask * (posr > offv - float(w))
        return slot, mask.reshape((b, 1, 1, max_len))

    def generate(self, tokens, max_new_tokens, temperature=0.0,
                 top_k=0, seed=0, rolling=False,
                 cache_dtype="float32"):
        """Autoregressive generation with a KV cache.

        tokens: (B, S) prompt NDArray.  Greedy when ``temperature=0``;
        otherwise softmax sampling with optional top-k truncation.
        Each step reuses ONE compiled program — positions ride the
        dynamic rope offset and the cache mask, so nothing recompiles
        as the sequence grows.  ``rolling=True`` (sliding-window
        models) bounds cache memory at O(W) via the rolling buffer.
        Returns (B, S + max_new_tokens).
        """
        import numpy as np
        from .. import ndarray as nd
        b, s = tokens.shape
        max_len = s + max_new_tokens
        caches = self.init_cache(b, max_len, ctx=tokens.context,
                                 rolling=rolling, dtype=cache_dtype)
        rng = np.random.RandomState(seed)
        out_tokens = [tokens.asnumpy()]
        logits = self.prefill(tokens, caches)  # one batched program
        for step_i in range(max_new_tokens):
            # float64 softmax: float32 normalization residue can make
            # np.random.choice reject the distribution
            lg = logits.asnumpy().astype(np.float64)
            if temperature and temperature > 0:
                lg = lg / temperature
                if top_k and top_k > 0:
                    kk = min(int(top_k), lg.shape[-1])
                    kth = np.sort(lg, axis=-1)[:, -kk][:, None]
                    lg = np.where(lg < kth, -np.inf, lg)
                p = np.exp(lg - lg.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                nxt = np.stack([rng.choice(p.shape[1], p=p[i])
                                for i in range(b)])
            else:
                nxt = lg.argmax(-1)
            host_tok = nxt.astype("float32").reshape(b, 1)
            out_tokens.append(host_tok)  # host already has it
            cur = nd.array(host_tok, ctx=tokens.context)
            if step_i < max_new_tokens - 1:  # last logits never read
                logits = self.decode_step(cur, caches, s + step_i)
        return nd.array(np.concatenate(out_tokens, axis=1),
                        ctx=tokens.context)

    def generate_beam(self, tokens, max_new_tokens, beam_size=4,
                      eos_id=None, alpha=1.0):
        """Beam-search generation over the KV-cache decoder.

        Reuses the generic :class:`~.nmt.BeamSearchSampler` (reference
        GluonNLP beam search): the flat beam axis is batch·beam, the
        per-layer caches are the reordered states, and the prompt is
        prefilled once per beam.  ``eos_id=None`` disables early stop
        (all beams run the full ``max_new_tokens``).  Returns
        ``(sequences (B, beam, S+<=N), scores (B, beam))`` sorted
        best-first, sequences INCLUDING the prompt.
        """
        import numpy as np
        from .. import ndarray as nd
        from .nmt import BeamSearchSampler, BeamSearchScorer

        b, s = tokens.shape
        k = int(beam_size)
        max_len = s + max_new_tokens
        # prefill ONCE per batch row, then replicate the filled caches
        # per beam (row-major repeat matches the sampler's i*k+j flat
        # layout) — K-fold less prompt compute than prefilling B*K
        # identical rows
        caches_b = self.init_cache(b, max_len, ctx=tokens.context)
        self.prefill(tokens, caches_b)
        caches = [nd.repeat(c, repeats=k, axis=0) for c in caches_b]
        last = nd.repeat(tokens[:, -1:], repeats=k, axis=0)

        def decoder(tok, step_idx, states):
            # step 0 re-writes position s-1 with the same K/V (a
            # no-op) and reproduces the prefill logits — so the
            # sampler's uniform "decode from the start token" contract
            # needs no special first step
            lg = self.decode_step(tok, states, s - 1 + step_idx)
            return nd.log_softmax(lg, axis=-1), states

        sampler = BeamSearchSampler(
            beam_size=k,
            eos_id=-1 if eos_id is None else int(eos_id),
            scorer=BeamSearchScorer(alpha=alpha),
            max_length=max_new_tokens + 1)
        samples, scores, lens = sampler(decoder, last, caches, b)
        # samples begin with the (repeated) last prompt token: splice
        # the full prompt in front of the continuation
        samp = samples.asnumpy().astype(np.int64)[:, :, 1:]
        prompt = tokens.asnumpy().astype(np.int64)
        out = np.concatenate(
            [np.repeat(prompt[:, None], k, axis=1), samp], axis=2)
        return (nd.array(out.astype("f4"), ctx=tokens.context),
                scores)

    def generate_fused(self, tokens, max_new_tokens, temperature=0.0,
                       top_k=0, seed=0, rolling=False,
                       cache_dtype="float32"):
        """Whole-generation as ONE compiled program.

        Same contract as :meth:`generate`, but prefill + every decode
        step run inside a single jit with the sampling loop as
        ``lax.scan`` and the KV cache as the scan carry — the
        TPU-idiomatic serving shape.  The per-step path pays one host
        round trip per token (against microseconds of compute for
        small models); this path pays one dispatch for the whole
        sequence.  Sampling uses on-device
        ``jax.random.categorical`` (seeded, reproducible) instead of
        the per-step path's host ``np.random`` — same distribution,
        different stream.  Compiled once per (batch, prompt_len,
        max_new_tokens, temperature>0, top_k) signature.
        """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from .. import ndarray as nd
        from ..ndarray.ndarray import NDArray
        from ..gluon import block as block_mod

        ctx = tokens.context
        if max_new_tokens <= 0:
            # fresh array like generate() (callers may mutate the
            # result in place; aliasing the prompt would corrupt it)
            return tokens.copy()
        b, s = tokens.shape
        max_len = s + max_new_tokens
        params = [p.data(ctx) for p in
                  self.collect_params().values()]
        sample = bool(temperature and temperature > 0)
        # top_k only shapes the program when sampling — greedy ignores
        # it, and including it in the key would compile a duplicate
        kk = min(int(top_k), self.model.vocab_size) \
            if (top_k and sample) else 0

        spec = self.state_spec(
            b, self._rolling_cache_len(max_len, rolling), cache_dtype)

        key = (b, s, max_new_tokens, sample, kk, rolling,
               str(cache_dtype), str(tokens.dtype))
        cache = getattr(self, "_gen_fused_cache", None)
        if cache is None:
            cache = self._gen_fused_cache = {}
        fn = cache.get(key)
        if fn is None:
            def traced(param_vals, tok_val, key_data, temp_val):
                with block_mod.tracing_scope(params, param_vals):
                    # caches hold activations in the declared cache
                    # dtype (a FLOAT dtype — int tokens once leaked
                    # int32 caches here, truncating every K/V write;
                    # bf16 halves decode cache bandwidth)
                    shells = [NDArray(jnp.zeros(shp, jnp.dtype(dt)),
                                      ctx=ctx) for _n, _k, shp, dt in spec]
                    toks = NDArray(tok_val, ctx=ctx)
                    logits0 = self.prefill(toks, shells)._data

                    def pick(lg, k_step):
                        if not sample:
                            return jnp.argmax(lg, axis=-1)
                        lg = lg.astype(jnp.float32) / temp_val
                        if kk:
                            kth = lax.top_k(lg, kk)[0][:, -1:]
                            lg = jnp.where(lg < kth, -jnp.inf, lg)
                        return jax.random.categorical(k_step, lg)

                    def body(carry, _):
                        tok, off, k, flat = carry
                        k, sub = jax.random.split(k)
                        cshells = [NDArray(c, ctx=ctx) for c in flat]
                        lg = self.decode_step(
                            NDArray(tok, ctx=ctx), cshells,
                            NDArray(off, ctx=ctx))._data
                        nxt = pick(lg, sub).astype(tok.dtype)
                        nxt = nxt.reshape((b, 1))
                        new_flat = tuple(c._data for c in cshells)
                        return (nxt, off + 1.0, k, new_flat), \
                            nxt[:, 0]

                    k0 = jax.random.wrap_key_data(key_data)
                    k0, sub0 = jax.random.split(k0)
                    first = pick(logits0, sub0).astype(
                        tok_val.dtype).reshape((b, 1))
                    flat0 = tuple(c._data for c in shells)
                    off0 = jnp.asarray(float(s), jnp.float32)
                    (_, _, _, _), toks_out = lax.scan(
                        body, (first, off0, k0, flat0), None,
                        length=max_new_tokens - 1) \
                        if max_new_tokens > 1 else ((None,) * 4,
                                                    jnp.zeros(
                                                        (0, b),
                                                        tok_val.dtype))
                    # sequence: prompt + first + scanned tokens
                    gen = jnp.concatenate(
                        [first, toks_out.T.astype(tok_val.dtype)],
                        axis=1)
                    return jnp.concatenate([tok_val, gen], axis=1)

            fn = cache[key] = jax.jit(traced)

        kd = jax.random.key_data(
            jax.random.key(int(seed)))
        out = fn([p._data for p in params], tokens._data, kd,
                 jnp.asarray(float(temperature or 1.0), jnp.float32))
        return NDArray(out, ctx=ctx)

    def loss(self, tokens, vocab_chunk=None):
        """Next-token cross-entropy over ``tokens`` (B, S) → scalar.

        ``vocab_chunk`` (or automatically at vocab ≥ 32768) streams
        the LM head through ``chunked_softmax_ce``: the (B·S, V)
        logits tensor — 16.8 GB f32 at Llama-3-8B b8 s4096, over a
        v5e's HBM — is never materialized; activation memory is
        O(B·S·chunk) with the slab recomputed in backward."""
        from .. import ndarray as nd
        from ..gluon.loss import SoftmaxCrossEntropyLoss
        v = self.model.vocab_size
        if vocab_chunk is None and v >= 32768:
            vocab_chunk = 8192
        if vocab_chunk:
            h = self.model(tokens)                     # (B, S, U)
            u = self.model._units
            hid = nd.slice_axis(h, axis=1, begin=0,
                                end=-1).reshape((-1, u))
            labels = nd.slice_axis(tokens, axis=1, begin=1,
                                   end=None).reshape((-1,))
            per_row = nd.chunked_softmax_ce(
                hid, self._head_weight(h.context), labels,
                chunk=int(vocab_chunk))
            return per_row.mean()
        logits = self(tokens)
        sce = SoftmaxCrossEntropyLoss()
        b, s, v = logits.shape
        pred = nd.slice_axis(logits, axis=1, begin=0,
                             end=-1).reshape((-1, v))
        labels = nd.slice_axis(tokens, axis=1, begin=1,
                               end=None).reshape((-1,))
        return sce(pred, labels).mean()


_LLAMA_SPECS = {
    # test-size config (trains in seconds on the CPU backend)
    "llama_tiny": dict(units=64, hidden=176, num_layers=2, num_heads=4,
                       num_kv_heads=2, rope_base=10000.0),
    # Llama-3-8B geometry (vocab passed by caller; default 128256)
    "llama3_8b": dict(units=4096, hidden=14336, num_layers=32,
                      num_heads=32, num_kv_heads=8,
                      rope_base=500000.0),
    # Mistral-style sliding-window test config: band of 32 positions —
    # the kernels skip out-of-band blocks, O(S·W) attention
    "mistral_tiny": dict(units=64, hidden=176, num_layers=2,
                         num_heads=4, num_kv_heads=2,
                         rope_base=10000.0, sliding_window=32),
    # Mistral-7B-v0.1 geometry (sliding_window=4096)
    "mistral_7b": dict(units=4096, hidden=14336, num_layers=32,
                       num_heads=32, num_kv_heads=8,
                       rope_base=10000.0, sliding_window=4096),
}


def get_llama(name, vocab_size=32000, attn_impl="sdpa", **kwargs):
    if name not in _LLAMA_SPECS:
        raise MXNetError(f"unknown llama config {name!r}; options "
                         f"{sorted(_LLAMA_SPECS)}")
    spec = dict(_LLAMA_SPECS[name])
    spec.update(kwargs)
    return LlamaModel(vocab_size=vocab_size, attn_impl=attn_impl,
                      **spec)


def llama_tiny(**kwargs):
    return get_llama("llama_tiny", **kwargs)


def llama3_8b(vocab_size=128256, **kwargs):
    return get_llama("llama3_8b", vocab_size=vocab_size, **kwargs)
