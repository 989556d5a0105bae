"""Pangu Ultra MoE (latent attention + routed experts) against its plain
reference (``models/pangu_moe_reference.py``), at ``pangu_moe_tiny`` on
the CPU, float32, seeded weights: 1 dense + 4 expert layers, 4 heads of
16 + 8 query/key and 16 value features over a hidden size of 64, a query
latent of 24 and a key-value latent of 16 (a page row is 16 + 8 = 24
numbers), 16 experts top-4.

TOLERANCE.  Program and reference compute the same float32 mathematics
in another order: the program's decode ABSORBS the up-projections into
the query and the output and attends the latent rows themselves, the
reference expands a key and a value per head for every position; the
program sorts and groups the held experts' products, the reference loops
over experts.  They differ by float32 rounding carried through 5 layers:
at most 1.1e-6 of the largest |logit| was read.  The limit is ``TOL`` =
2e-5: eighteen times that reading, and far under what any omitted term
gives (the smallest read in ``test_each_omission_fails`` is 0.53, a
query latent without its norm: an omission moves the picks too): room on
both sides.

ROUTING IS DISCRETE (``tests/test_afmoe.py`` says why): the seeded
weights leave a margin of at least ``MARGIN`` between the 4th and 5th
score of every row these tests route; ``_reference`` asserts it.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import PanguMoeForCausalLM, pangu_moe_tiny
from mxnet_tpu.models import pangu_moe_reference as ref
from mxnet_tpu.serving import KVCachePool, Server

V = 256
TOL = 2e-5
MARGIN = 2e-6
ROW = 16 + 8            # a page row: kv_rank + rope_dim
LAYERS = 5


def _build(seed=0, **kwargs):
    mx.random.seed(seed)
    lm = PanguMoeForCausalLM(pangu_moe_tiny(vocab_size=V, **kwargs))
    lm.initialize(mx.init.Xavier())
    rng = np.random.RandomState(7)
    for name, p in lm.collect_params().items():
        if name.endswith("gamma"):
            p.set_data(nd.array(1 + 0.1 * rng.randn(*p.shape).astype("f4")))
        elif name.endswith("weight") and len(p.shape) == 3:
            p.set_data(nd.array(0.2 * rng.randn(*p.shape).astype("f4")))
    return lm


def _share(whole, first, count):
    """A net holding experts ``first .. first + count - 1`` of ``whole``'s,
    every other weight the same."""
    lm = PanguMoeForCausalLM(pangu_moe_tiny(vocab_size=V,
                                            experts_held=(first, count)))
    lm.initialize()
    for p, q in zip(lm.collect_params().values(),
                    whole.collect_params().values()):
        value = q.data().asnumpy()
        if "_experts_" in p.name:
            value = value[first:first + count]
        p.set_data(nd.array(value))
    return lm


@pytest.fixture(scope="module")
def net():
    return _build()


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, V, n).astype("f4")


S = 32      # every reference runs at this length: its pieces compile once


def _reference(lm, toks, precision="float32", selections=None):
    """(logits, routing) of the plain reference over ``toks`` (at most
    ``S`` of them: the model is causal, so the sequence is padded to ``S``
    and the padding's rows are cut off again)."""
    cfg, held = ref.config_of(lm)
    n = len(toks)
    padded = np.concatenate([toks, np.ones(S - n, "f4")])
    if selections is not None:
        selections = np.concatenate(
            [selections, np.zeros((S - n,) + selections.shape[1:],
                                  selections.dtype)])
    routing = {}
    want = ref.forward_logits(ref.weights_of(lm), padded, cfg, precision,
                              held, selections=selections, routing=routing)
    routing = {k: v[:n] for k, v in routing.items()}
    assert routing["margin"].min() > MARGIN, routing["margin"].min()
    return want[:n], routing


def _err(got, want):
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


def _close(got, want, what=""):
    err = _err(got, want)
    assert err <= TOL, f"{what}: {err:.2e} of the largest value"


def test_the_presets_are_one_dense_layer_and_four_expert_layers():
    m = pangu_moe_tiny(vocab_size=V)
    assert [l.dense for l in m.layers] == [True] + [False] * 4
    assert m.experts_held == (0, 16) and m.row_width == ROW
    # no selection bias in this family: the routers hold a matrix alone
    assert not [n for n in m.collect_params() if n.endswith("router_bias")]
    with pytest.raises(mx.MXNetError, match="experts_held"):
        pangu_moe_tiny(vocab_size=V, experts_held=(12, 8))
    with pytest.raises(mx.MXNetError, match="rope_dim"):
        pangu_moe_tiny(vocab_size=V, rope_dim=7)
    with pytest.raises(mx.MXNetError, match="unknown pangu_moe"):
        mx.models.get_pangu_moe("pangu_moe_huge")


def test_full_forward_matches_the_reference(net):
    toks = _tokens(1, S)
    want, _ = _reference(net, toks)
    _close(net(nd.array(toks[None])).asnumpy()[0], want, "full forward")


@pytest.fixture
def take_the_walk(monkeypatch):
    """``take(blk)``: from here to the test's end the decode attention is
    lowered as a TPU with a positions-minor page lowers it: the kernel,
    through the Pallas interpreter, ``blk`` positions a block.  The
    engine keeps an op's executable by name and avals, so what either
    lowering compiled is dropped on the way in and on the way out."""
    from mxnet_tpu import engine
    from mxnet_tpu.ops import latent_attention as la
    from mxnet_tpu.ops import page_write
    from mxnet_tpu.ops.registry import get_op

    def forget():
        la._walk_rows.cache_clear()
        for op in ("_contrib_LatentAttention",
                   "_contrib_LatentAttentionWalked"):
            engine.drop_cached(op)

    def take(blk):
        # jax keeps a trace by the traced function's identity: the count
        # (no attrs, so the engine jits the registered function itself)
        # is traced through a stand-in that dies with the test
        count = get_op("_contrib_LatentAttentionWalked")
        monkeypatch.setattr(count, "fcompute", lambda *a, f=count.fcompute:
                            f(*a))
        monkeypatch.setattr(page_write, "_positions_on_lanes",
                            lambda shape, dtype: True)
        monkeypatch.setattr(la, "_LANES", min(blk, 16))     # toy tiles
        monkeypatch.setattr(la, "_BLK", blk)
        monkeypatch.setattr(la, "_INTERPRET", True)
        forget()
        return la

    yield take
    monkeypatch.undo()
    forget()


@pytest.mark.parametrize("stale", ["zeros", "an_evicted_requests_rows",
                                   "kernel"])
def test_expanded_prefill_then_absorbed_decode_rows_at_their_own_offsets(
        net, stale, take_the_walk, monkeypatch):
    """The call shapes the server's programs make: a right-padded batch
    prefilled (EXPANDED) at each row's ``last_pos``, then one token a row
    (ABSORBED) at (B,) offsets, against the reference's one full forward.
    A page that an evicted request filled to its end reads the same: rows
    past a slot's offset are never attended.  ``kernel``: the same over
    an evicted request's rows with the decode attention walking the page
    in blocks of 8 positions (the rows cross three block edges), the
    float32 softmax held at ``TOL`` THROUGH the kernel."""
    lens, new = (6, 14), 18
    seqs = [_tokens(10 + i, n + new) for i, n in enumerate(lens)]
    wants = [_reference(net, s)[0] for s in seqs]
    if stale == "kernel":
        la = take_the_walk(8)
        walks = []
        monkeypatch.setattr(la, "_walk_call", lambda *a, f=la._walk_call:
                            walks.append(1) or f(*a))
    state = net.init_cache(2, max(lens) + new)
    assert [b.shape for b in state] == [(2, S, ROW)] * LAYERS
    if stale != "zeros":
        rng = np.random.RandomState(5)
        for page in state:
            page[:] = nd.array(30.0 * rng.randn(*page.shape).astype("f4"))
    # prefill writes the padded prompt's 16 rows; rows 16.. stay as they
    # were
    prompt = np.zeros((2, 16), "f4")
    for i, n in enumerate(lens):
        prompt[i, :n] = seqs[i][:n]
    last = nd.array(np.array(lens, "f4") - 1)
    got = net.prefill(nd.array(prompt), state, last).asnumpy()
    for i, n in enumerate(lens):
        _close(got[i], wants[i][n - 1], f"prefill row {i}")
    for step in range(new):
        tok = np.array([[seqs[i][n + step]] for i, n in enumerate(lens)])
        off = nd.array(np.array(lens, "f4") + step)
        got = net.decode_step(nd.array(tok), state, off).asnumpy()
        for i, n in enumerate(lens):
            _close(got[i], wants[i][n + step], f"row {i} step {step}")
    if stale == "kernel":
        assert walks, "the decode steps never reached the kernel"


def test_served_tokens_are_the_references_argmax_and_generates(net):
    """Through ``serving.Server``: four prompts of different lengths over
    a two-slot bucket, so two of them take a slot an evicted request has
    left its rows in; every served token is the argmax of the reference's
    full forward of what came before, and what ``generate`` gives."""
    srv = Server(net, buckets=[(2, 16)], max_new_tokens=16)
    prompts = [_tokens(20 + i, n) for i, n in enumerate((15, 7, 4, 11))]
    new = (16, 9, 16, 12)
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    srv.run()
    for req, prompt, n in zip(reqs, prompts, new):
        toks = req.tokens()
        want, _ = _reference(net, toks[:-1])
        assert (want[req.prompt_len - 1:].argmax(-1)
                == toks[req.prompt_len:]).all()
        assert (net.generate(nd.array(prompt[None]), n).asnumpy()[0]
                == toks).all()
    pool, = srv._pools.values()
    assert pool.bytes_by_kind() == {"kv_latent": LAYERS * 2 * 32 * ROW * 4}


# (heads, nope, rope, v, kv rank, positions): the tiny model's, heads of
# one width throughout, and a rope part wider than the rest
@pytest.mark.parametrize("h,dn,dr,dv,rkv,c", [
    (4, 16, 8, 16, 16, 24), (2, 8, 8, 8, 12, 9), (3, 4, 16, 6, 10, 17)])
def test_absorbed_equals_expanded(h, dn, dr, dv, rkv, c):
    """The op's two modes on the same inputs: the EXPANDED output at
    position t is the ABSORBED output of query t over a page holding the
    same rows, whatever lies past t."""
    rng = np.random.RandomState(h)
    b = 2
    q = rng.randn(b, c, h, dn + dr).astype("f4")
    rows = rng.randn(b, c, rkv + dr).astype("f4")
    w = (rng.randn(h * (dn + dv), rkv) / math.sqrt(rkv)).astype("f4")
    attrs = dict(nope_dim=dn, v_dim=dv)
    want = nd._contrib_LatentAttention(
        nd.array(q), nd.array(rows), nd.array(w), **attrs).asnumpy()
    assert want.shape == (b, c, h * dv)
    page = np.concatenate([rows, 50.0 * rng.randn(b, 7, rkv + dr)
                           .astype("f4")], axis=1)
    for t in (0, c // 2, c - 1):
        offs = np.array([t, max(t - 1, 0)], "f4")   # rows at their own
        got = nd._contrib_LatentAttention(
            nd.array(np.stack([q[0, offs[0].astype(int)],
                               q[1, offs[1].astype(int)]])[:, None]),
            nd.array(page), nd.array(w), nd.array(offs), use_offset=True,
            **attrs).asnumpy()
        for i in range(b):
            _close(got[i, 0], want[i, int(offs[i])], f"t={t} row {i}")


def _walk_case(dtype, c, offs, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    b, h, rkv, dr = len(offs), 4, 16, 8
    qq = jnp.asarray(rng.randn(b, h, rkv + dr), dtype)
    page = jnp.asarray(rng.randn(b, c, rkv + dr), dtype)
    return qq, page, jnp.asarray(np.asarray(offs, "i4")), rkv, 24 ** -0.5


# blk 32: offsets at 0, blk - 1, blk, the page's end, and mixed in one
# batch; 80 is no multiple of blk (its last block starts early)
@pytest.mark.parametrize("c,offs", [
    (64, (0,) * 3), (64, (31,) * 3), (64, (32,) * 3), (64, (63,) * 3),
    (64, (0, 31, 32, 63, 17, 40)), (80, (0, 31, 32, 79, 64, 63)),
    (16, (0, 15, 7))])
@pytest.mark.parametrize("dtype,tol", [("float32", TOL), ("bfloat16", 1e-2)])
def test_the_walk_is_the_definition(take_the_walk, dtype, tol, c, offs):
    """The kernel (Pallas interpreter) against ``_attend_dense`` on the
    same operands: float32 at the model's own ``TOL`` (scores, running
    maximum, sum and accumulator are float32 in the kernel), bfloat16 at
    its rounding."""
    la = take_the_walk(32)
    qq, page, off, rkv, scale = _walk_case(dtype, c, offs)
    want = np.asarray(la._attend_dense(qq, page, off, rkv, scale)
                      .astype("float32"))
    got = la._walk_call(qq, page, off, rkv, scale)
    assert got.shape == want.shape and str(got.dtype) == dtype
    err = _err(got.astype("float32"), want)
    assert err <= tol, f"{err:.2e} of the largest value"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c", [64, 80])
def test_the_walk_reads_nothing_past_an_offset(take_the_walk, dtype, c):
    """What an evicted request left past a row's offset (large, finite)
    against zeros there: bit-equal outputs."""
    import jax.numpy as jnp
    la = take_the_walk(32)
    offs = (0, 31, 32, c - 2, 17, 40)
    qq, page, off, rkv, scale = _walk_case(dtype, c, offs, seed=3)
    past = np.arange(c)[None, :, None] > np.asarray(offs)[:, None, None]
    stale = jnp.where(past, jnp.asarray(1e4, page.dtype) * page, page)
    clean = jnp.where(past, jnp.zeros((), page.dtype), page)
    np.testing.assert_array_equal(
        np.asarray(la._walk_call(qq, stale, off, rkv, scale)
                   .astype("float32")),
        np.asarray(la._walk_call(qq, clean, off, rkv, scale)
                   .astype("float32")))


@pytest.mark.parametrize("path", ["dense", "walk", "walk_ragged_page",
                                  "page_off_the_lane_tiles"])
def test_walked_positions_are_those_of_the_path_taken(take_the_walk,
                                                      monkeypatch, path):
    """``_contrib_LatentAttentionWalked`` and the op share one decision:
    rows x page where the definition runs, the walked blocks x ``blk``
    where the kernel does (a count that said "walked" over a dense pass
    would be a false reading), and the op's output is the definition's
    either way."""
    from mxnet_tpu.ops import latent_attention as la
    c = {"walk_ragged_page": 80, "page_off_the_lane_tiles": 72}.get(path, 64)
    offs = np.array([0, 31, 32, c - 1, 40], "f4")
    rng = np.random.RandomState(1)
    ins = [nd.array(rng.randn(5, 1, 4, 24).astype("f4")),
           nd.array(rng.randn(5, c, 24).astype("f4")),
           nd.array((rng.randn(4 * 32, 16) / 4).astype("f4")),
           nd.array(offs)]
    attrs = dict(nope_dim=16, v_dim=16, use_offset=True)
    want = nd._contrib_LatentAttention(*ins, **attrs).asnumpy()
    if path != "dense":
        take_the_walk(32)
    calls = []
    monkeypatch.setattr(la, "_walk_call", lambda *a, f=la._walk_call:
                        calls.append(1) or f(*a))
    got = nd._contrib_LatentAttention(*ins, **attrs).asnumpy()
    walked = int(nd._contrib_LatentAttentionWalked(ins[1], ins[3]).asnumpy())
    if path in ("dense", "page_off_the_lane_tiles"):
        # 72 positions are no whole tiles of (here) 32 lanes
        assert not calls and walked == 5 * c
    else:
        assert calls and walked == 32 * sum(int(o) // 32 + 1 for o in offs)
    _close(got, want, path)


def test_the_walk_under_a_dp_plan_gathers_no_page(take_the_walk):
    """Four devices, the rows split over them: the partitioner is told
    rows are independent, each shard's kernel walks its own rows, and
    the compiled program holds no all-gather (of the page or of anything
    else)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    la = take_the_walk(32)
    offs = (0, 31, 32, 63, 17, 40, 5, 62)
    qq, page, off, rkv, scale = _walk_case("float32", 64, offs)
    by_rows = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("dp",)),
                            PartitionSpec("dp"))
    args = [jax.device_put(a, by_rows) for a in (qq, page, off)]
    fn = jax.jit(la._walk_rows(rkv, scale), out_shardings=by_rows)
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" not in text and "all-reduce" not in text
    _close(fn(*args), np.asarray(la._attend_dense(qq, page, off, rkv, scale)))


def test_the_op_refuses_shapes_that_do_not_fit():
    q, rows = nd.zeros((1, 4, 2, 12)), nd.zeros((1, 4, 10))
    with pytest.raises(Exception, match="do not fit"):
        nd._contrib_LatentAttention(q, rows, nd.zeros((2 * 16, 7)),
                                    nope_dim=8, v_dim=8)
    with pytest.raises(Exception, match="one query a row"):
        nd._contrib_LatentAttention(q, rows, nd.zeros((2 * 16, 6)),
                                    nd.zeros((1,)), nope_dim=8, v_dim=8,
                                    use_offset=True)
    with pytest.raises(Exception, match="attends its own rows"):
        nd._contrib_LatentAttention(q[:, :2], rows, nd.zeros((2 * 16, 6)),
                                    nope_dim=8, v_dim=8)


def test_the_shares_add_up_to_the_uncut_layer(net):
    """Four shares of 4 experts: their routed partial sums, with what
    every chip computes alike (the shared expert) counted once, are the
    uncut reference's layer output."""
    rng = np.random.RandomState(3)
    m = nd.array(rng.randn(1, S, 64).astype("f4"))
    whole = net.model.layers[2].ffn
    shared = whole.shared(m).asnumpy()
    parts, counted = 0, 0
    for first in (0, 4, 8, 12):
        share = _share(net, first, 4).model.layers[2].ffn
        out, (held, _touched), _sel = share.route(m)
        parts = parts + out.asnumpy() - shared
        counted += int(held.asnumpy())
    assert counted == S * 4             # every assignment lands once
    cfg, _ = ref.config_of(net)
    w = {k[len("layer2_"):]: v for k, v in ref.weights_of(net).items()
         if k.startswith("layer2_")}
    routed, _pick, margin = ref._routed(m.asnumpy()[0], w, cfg, (0, 16),
                                        None, "float32")
    assert float(margin.min()) > MARGIN
    want = np.asarray(routed) + np.asarray(ref._swiglu_jit(
        m.asnumpy()[0], w["moe_shared_gateup_weight"],
        w["moe_shared_down_weight"], precision="float32"))
    _close(parts[0] + shared[0], want, "sum of shares")
    _close(whole.route(m)[0].asnumpy()[0], want, "uncut layer")


def test_a_share_of_the_model_matches_the_reference_given_the_same_share(
        net):
    share = _share(net, 4, 8)
    toks = _tokens(4, S)
    want, _ = _reference(share, toks)
    _close(share(nd.array(toks[None])).asnumpy()[0], want, "share (4, 8)")
    whole, _ = _reference(net, toks)
    assert _err(want, whole) > 100 * TOL      # absent experts are left out


def _no_norm(mp, norm):
    mp.setattr(norm, "hybrid_forward",
               lambda F, x, gamma=None: x.astype("float32"))


def _query_times(mp, lm, factor):
    """Every layer's attention sees its query's features times ``factor``
    (dn + dr of them, one factor each)."""
    cls = type(lm.model.layers[0].attn)
    attend = cls._attend
    mp.setattr(cls, "_attend", lambda self, q, rows, *off: attend(
        self, q * nd.array(np.asarray(factor, "f4")), rows, *off))


def _k_r_unrotated(mp, lm):
    rope = nd.rope
    # the shared key is the one rotated tensor with ONE head
    mp.setattr(nd, "rope", lambda x, **kw: x if x.shape[2] == 1
               else rope(x, **kw))


OMISSIONS = {
    "query_latent_norm": lambda mp, lm: [
        _no_norm(mp, layer.attn.q_norm) for layer in lm.model.layers],
    "kv_latent_norm": lambda mp, lm: [
        _no_norm(mp, layer.attn.kv_norm) for layer in lm.model.layers],
    "rotation_of_k_r": _k_r_unrotated,
    "rope_part_of_the_score": lambda mp, lm: _query_times(
        mp, lm, [1.0] * 16 + [0.0] * 8),
    "scale_by_nope_width_alone": lambda mp, lm: _query_times(
        mp, lm, math.sqrt(24 / 16)),
    "route_scale": lambda mp, lm: [
        mp.setitem(layer.ffn._attrs, "route_scale", 1.0)
        for layer in lm.model.layers if not layer.dense],
    "attention_post_norm": lambda mp, lm: [
        _no_norm(mp, layer.ln2) for layer in lm.model.layers],
    "ffn_post_norm": lambda mp, lm: [
        _no_norm(mp, layer.ln4) for layer in lm.model.layers],
}


@pytest.mark.parametrize("omitted", sorted(OMISSIONS))
def test_each_omission_fails(net, monkeypatch, omitted):
    """A program that leaves one term out is not inside the tolerance."""
    toks = _tokens(8, S)
    want, _ = _reference(net, toks)
    _close(net(nd.array(toks[None])).asnumpy()[0], want, "sound")
    OMISSIONS[omitted](monkeypatch, net)
    err = _err(net(nd.array(toks[None])).asnumpy()[0], want)
    assert err > 100 * TOL, f"without {omitted}: only {err:.2e}"


def test_statistics_are_a_numpy_count():
    """After a padded prefill and after a decode step ``last_statistics``
    holds, in the order of ``statistics``: the expert layers' four counts
    (a NumPy count of the reference's picks), then the positions a
    request had written among those the latent attention ran over, those
    positions, and the attention-layer calls."""
    lm = _share(_build(), 4, 8)
    first, count = lm.model.experts_held
    lens = (6, 13)
    seqs = [_tokens(30 + i, n + 1) for i, n in enumerate(lens)]
    picks = [_reference(lm, s)[1]["picked"] for s in seqs]  # (S, 4, k)

    def count_of(rows):
        """rows: one (layers, k) array a routed row of one call."""
        stacked = np.stack(rows)                        # (rows, 4, k)
        held = (stacked >= first) & (stacked < first + count)
        touched = sum(len(np.unique(stacked[:, l][held[:, l]]))
                      for l in range(stacked.shape[1]))
        return [held.sum(), touched, stacked.shape[0] * stacked.shape[1],
                stacked.shape[1]]

    state = lm.init_cache(2, 24)
    prompt = np.zeros((2, 16), "f4")
    for i, n in enumerate(lens):
        prompt[i, :n] = seqs[i][:n]
    lm.prefill(nd.array(prompt), state, nd.array(np.array(lens, "f4") - 1))
    got = [int(c.asnumpy()) for c in lm.last_statistics[:7]]
    assert got[:4] == count_of([picks[i][t] for i, n in enumerate(lens)
                                for t in range(n)])
    # 19 prompt positions of the 32 the expanded attention ran over
    assert got[4:] == [LAYERS * sum(lens), LAYERS * 2 * 16, LAYERS]
    tok = np.array([[seqs[i][n]] for i, n in enumerate(lens)])
    lm.decode_step(nd.array(tok), state, nd.array(np.array(lens, "f4")))
    got = [int(c.asnumpy()) for c in lm.last_statistics[:7]]
    assert got[:4] == count_of([picks[i][n] for i, n in enumerate(lens)])
    # each row attends its whole page of 24 (the dense lowering: no TPU
    # here; the kernel's count is
    # ``test_walked_positions_are_those_of_the_path_taken``); offset + 1
    # of them are live
    assert got[4:] == [LAYERS * (7 + 14), LAYERS * 2 * 24, LAYERS]
    assert [n for n, _doc in lm.statistics] == [
        "mxtpu_moe_assignments_held_total",
        "mxtpu_moe_experts_touched_total", "mxtpu_moe_routed_rows_total",
        "mxtpu_moe_layer_calls_total", "mxtpu_mla_live_positions_total",
        "mxtpu_mla_page_positions_total", "mxtpu_mla_layer_calls_total"]


def test_served_counters_rise_by_the_programs_counts(net):
    """The counts ride out behind each dispatch's tokens: one request
    alone, prompt 5 + 4 new tokens = one prefill and three decodes."""
    from mxnet_tpu import telemetry

    def counters():
        c = telemetry.snapshot()["counters"]
        return [c.get(name, 0) for name, _doc in net.statistics[4:]]

    srv = Server(net, buckets=[(2, 8)], max_new_tokens=4)
    heard = []
    srv.statistics_listener = lambda kind, cols, counts, rows: heard.append(
        (kind, [int(c) for c in counts[4:]]))
    before = counters()
    srv.submit(_tokens(40, 5))
    srv.run()
    # prefill: 5 live of the bucket's 8; decode at offsets 5, 6, 7 of a
    # 2-slot page of 12, the idle slot's row at its own offset 0
    assert heard[0] == ("prefill", [LAYERS * 5, LAYERS * 8, LAYERS])
    assert [h for h in heard[1:]] == [
        ("decode", [LAYERS * (off + 1 + 1), LAYERS * 2 * 12, LAYERS])
        for off in (5, 6, 7)]
    assert [a - b for a, b in zip(counters(), before)] == [
        sum(h[1][i] for h in heard) for i in range(3)]


def test_state_spec_names_one_latent_page_a_layer_and_nothing_per_head(net):
    spec = net.state_spec(3, 40, "bfloat16")
    assert spec == [(f"layer{i}_latent", "kv_latent", (3, 40, ROW),
                     "bfloat16") for i in range(LAYERS)]
    pool = KVCachePool(net, slots=3, cache_len=40, dtype="bfloat16")
    assert pool.bytes_by_kind() == {"kv_latent": LAYERS * 3 * 40 * ROW * 2}
    # 4 heads x (16 + 8 + 16) per-head K,V features would be 160 a position
    assert ROW * 6 < 4 * (16 + 8 + 16)
    with pytest.raises(mx.MXNetError, match="floating"):
        net.state_spec(1, 8, "int32")
    from mxnet_tpu.serving.kvcache import check_spec
    with pytest.raises(mx.MXNetError, match="kv_latents"):
        check_spec([("x", "kv_latents", (3, 4), "float32")], 3)


def test_each_precision_rounds_more_than_the_one_above(net):
    toks = _tokens(9, S)
    cfg, held = ref.config_of(net)
    exact, stated, float8 = (
        ref.forward_logits(ref.weights_of(net), toks, cfg, p, held)
        for p in ("float32", "stated", "float8"))
    assert TOL < _err(stated, exact) < _err(float8, exact)
    with pytest.raises(KeyError):
        ref.forward_logits(ref.weights_of(net), toks, cfg, "float16", held)


def test_given_selections_take_the_place_of_the_references_own(net):
    toks = _tokens(11, S)
    want, routing = _reference(net, toks)
    same, _ = _reference(net, toks, selections=routing["picked"])
    np.testing.assert_array_equal(same, want)
    other = routing["picked"].copy()
    other[:, 1] = (other[:, 1] + 1) % 16        # layer 2 picks its neighbours
    moved, own = _reference(net, toks, selections=other)
    assert _err(moved, want) > 100 * TOL
    # what the reference itself would have picked is still reported
    assert (own["picked"][:, 0] == routing["picked"][:, 0]).all()


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    src = inspect.getsource(ref)
    code = src[src.index('"""', 3) + 3:src.index("def weights_of")]
    assert "mxnet_tpu" not in code and "import" in code
