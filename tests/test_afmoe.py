"""AFMoE (Arcee Trinity) against its plain reference
(``models/afmoe_reference.py``), at ``afmoe_tiny`` on the CPU, float32,
seeded weights: 1 dense + 4 expert layers (window, window, full, window
after the dense one), 16 experts top-4 with a nonzero selection bias,
window 8, heads of 32 over a hidden size of 64.

TOLERANCE.  Program and reference compute the same float32 mathematics
in another order (a sorted, grouped product over the held experts against
a loop over experts, a fused softmax over grouped heads against one head
at a time, a rolling K,V buffer against a whole sequence), so they differ
by float32 rounding carried through 5 layers: at most 1.6e-6 of the
largest |logit| was read.  The limit is ``TOL`` = 2e-5: twelve times
that reading, and far under what any omitted term gives (the smallest
read in ``test_each_omission_fails`` is 0.52, without ``route_scale``):
room on both sides.

ROUTING IS DISCRETE.  A row whose 4th and 5th biased scores lie closer
than the rounding noise of the two computations could pick another expert
in each and move its logits by percents.  In float32 on one machine the
same sums give the same bits, and the seeded weights leave a margin of at
least ``MARGIN`` between the 4th and 5th biased score of every row that
these tests route (1.6e-4 read at the closest row; the scores agree to
~1e-7), so no pick flips; ``_reference`` asserts the margin.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.models import AfmoeForCausalLM, afmoe_tiny
from mxnet_tpu.models import afmoe_reference as ref
from mxnet_tpu.models.afmoe import FULL, SLIDING

V = 256
TOL = 2e-5
MARGIN = 2e-6
WINDOW = 8


def _build(seed=0, **kwargs):
    mx.random.seed(seed)
    lm = AfmoeForCausalLM(afmoe_tiny(vocab_size=V, **kwargs))
    lm.initialize(mx.init.Xavier())
    rng = np.random.RandomState(7)
    for name, p in lm.collect_params().items():
        if name.endswith("router_bias"):
            # the selection bias starts at 0: give it values, or the term
            # that picks without weighing would go untested
            p.set_data(nd.array(0.3 * rng.randn(*p.shape).astype("f4")))
        elif name.endswith("gamma"):
            p.set_data(nd.array(1 + 0.1 * rng.randn(*p.shape).astype("f4")))
        elif name.endswith("weight") and len(p.shape) == 3:
            p.set_data(nd.array(0.2 * rng.randn(*p.shape).astype("f4")))
    return lm


def _share(whole, first, count, **kwargs):
    """A net holding experts ``first .. first + count - 1`` of ``whole``'s,
    every other weight the same."""
    lm = AfmoeForCausalLM(afmoe_tiny(vocab_size=V,
                                     experts_held=(first, count), **kwargs))
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


def test_the_presets_are_one_dense_layer_and_one_period():
    m = afmoe_tiny(vocab_size=V)
    assert [l.kind for l in m.layers] == [SLIDING] * 3 + [FULL, SLIDING]
    assert [l.dense for l in m.layers] == [True] + [False] * 4
    assert m.experts_held == (0, 16)
    with pytest.raises(mx.MXNetError, match="experts_held"):
        afmoe_tiny(vocab_size=V, experts_held=(12, 8))
    with pytest.raises(mx.MXNetError, match="layer_types"):
        afmoe_tiny(vocab_size=V, layer_types=("sliding", FULL))


def test_full_forward_matches_the_reference(net):
    toks = _tokens(1, S)
    want, routing = _reference(net, toks)
    _close(net(nd.array(toks[None])).asnumpy()[0], want, "full forward")


def test_prefill_then_decode_across_three_windows_rows_at_their_own_offsets(
        net):
    """The call shapes the server's programs make: a right-padded batch
    prefilled at each row's ``last_pos``, then one token a row at (B,)
    offsets, the contexts ending 3x past the window of 8."""
    lens, new = (6, 14), 18              # contexts of 24 = 3 x 8, and 32
    seqs = [_tokens(10 + i, n + new) for i, n in enumerate(lens)]
    wants = [_reference(net, s)[0] for s in seqs]
    state = net.init_cache(2, max(lens) + new)
    assert [b.shape[1] for b in state[:10:2]] == [WINDOW] * 3 + [S, WINDOW]
    prompt = np.zeros((2, 16), "f4")
    for i, n in enumerate(lens):
        prompt[i, :n] = seqs[i][:n]
    last = nd.array(np.array(lens, "f4") - 1)
    got = net.prefill(nd.array(prompt), state, last).asnumpy()
    for i, n in enumerate(lens):
        _close(got[i], wants[i][n - 1], f"prefill row {i}")
    # behind its counts every call leaves who was picked, row by row: the
    # reference's own account, -1 where a prompt's row is padding
    picked = [_reference(net, s)[1]["picked"] for s in seqs]
    rows = net.last_statistics[-1].asnumpy()
    assert rows.dtype == np.int32 and rows.shape == (2, 16, 16)
    for i, n in enumerate(lens):
        assert (rows[i, :n].reshape(n, 4, 4) == picked[i][:n]).all()
        assert (rows[i, n:] == -1).all()
    for step in range(new):
        tok = np.array([[seqs[i][n + step]] for i, n in enumerate(lens)])
        off = nd.array(np.array(lens, "f4") + step)
        got = net.decode_step(nd.array(tok), state, off).asnumpy()
        rows = net.last_statistics[-1].asnumpy()
        for i, n in enumerate(lens):
            _close(got[i], wants[i][n + step], f"row {i} step {step}")
            assert (rows[i, 0].reshape(4, 4) == picked[i][n + step]).all()


def test_served_tokens_are_the_references_argmax(net):
    """Through ``serving.Server``: three prompts of different lengths in
    one two-slot bucket, each run 3x past the window; every served token
    is the argmax of the reference's full forward of what came before."""
    from mxnet_tpu.serving import Server
    srv = Server(net, buckets=[(2, 16)], max_new_tokens=17)
    prompts = [_tokens(20 + i, n) for i, n in enumerate((7, 15, 11))]
    reqs = [srv.submit(p) for p in prompts]
    srv.run()
    for req in reqs:
        toks = req.tokens()
        want, _ = _reference(net, toks[:-1])
        assert (want[req.prompt_len - 1:].argmax(-1)
                == toks[req.prompt_len:]).all()


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


def test_the_bias_picks_and_does_not_weigh(net):
    toks = _tokens(5, S)
    want, routing = _reference(net, toks)
    # with the bias taken out of the SELECTION other experts are picked ...
    flat = {k: (np.zeros_like(np.asarray(v)) if k.endswith("router_bias")
                else v) for k, v in ref.weights_of(net).items()}
    cfg, held = ref.config_of(net)
    unbiased = {}
    ref.forward_logits(flat, toks, cfg, "float32", held, routing=unbiased)
    moved = (np.sort(routing["picked"][:, 0], -1)
             != np.sort(unbiased["picked"][:, 0], -1)).any(-1)
    assert moved.sum() >= 5               # top4(s + b) != top4(s)
    # ... and a reference that also WEIGHS by the bias is another model
    # (the op's arithmetic is in tests/test_moe.py)
    _close(net(nd.array(toks[None])).asnumpy()[0], want, "biased pick")


def test_a_full_layer_ignores_positions_and_a_window_layer_does_not(net):
    """Swap the two kinds at layers 3 and 4: the same weights are another
    model (RoPE moves with the window), and each is its own reference."""
    toks = _tokens(6, S)
    swapped = _share(net, 0, 16, layer_types=(SLIDING,) * 3 + (SLIDING, FULL))
    want, _ = _reference(net, toks)
    want_swapped, _ = _reference(swapped, toks)
    _close(swapped(nd.array(toks[None])).asnumpy()[0], want_swapped,
           "swapped kinds")
    assert _err(want_swapped, want) > 100 * TOL
    # under a full layer the K it stores is NOT rotated: position-free
    k_full = net.model.layers[3].attn.seq(
        nd.array(np.ones((1, 6, 64), "f4")))[1].asnumpy()[0]
    assert np.abs(k_full - k_full[0]).max() < 1e-6
    k_win = net.model.layers[4].attn.seq(
        nd.array(np.ones((1, 6, 64), "f4")))[1].asnumpy()[0]
    assert np.abs(k_win - k_win[0]).max() > 1e-2


def _no_norm(mp, norm):
    mp.setattr(norm, "hybrid_forward",
               lambda F, x, gamma=None: x.astype("float32"))


OMISSIONS = {
    "gate": lambda mp, lm: mp.setattr(
        type(lm.model.layers[0].attn), "_out",
        lambda self, o, g: self.o_proj(o.reshape((o.shape[0], o.shape[1],
                                                  -1)))),
    "qk_norms": lambda mp, lm: [
        _no_norm(mp, norm) for layer in lm.model.layers
        for norm in (layer.attn.q_norm, layer.attn.k_norm)],
    "route_scale": lambda mp, lm: [
        mp.setitem(layer.ffn._attrs, "route_scale", 1.0)
        for layer in lm.model.layers if not layer.dense],
    "sqrt_d": lambda mp, lm: mp.setattr(
        type(lm.model), "embed_scaled",
        lambda self, t: self.embed(t).astype("float32")),
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


def test_statistics_are_a_numpy_count_of_the_references_picks():
    """After a padded prefill and after a decode step ``last_statistics``
    holds, in the order of ``statistics``: assignments on held experts,
    held experts touched (summed over the expert layers' calls), rows
    routed, expert-layer calls; padded rows count nowhere."""
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
    got = [int(c.asnumpy()) for c in lm.last_statistics[:4]]
    assert got == count_of([picks[i][t] for i, n in enumerate(lens)
                            for t in range(n)])
    assert got[2] == sum(lens) * 4          # 32 padded positions: nowhere
    tok = np.array([[seqs[i][n]] for i, n in enumerate(lens)])
    lm.decode_step(nd.array(tok), state, nd.array(np.array(lens, "f4")))
    got = [int(c.asnumpy()) for c in lm.last_statistics[:4]]
    assert got == count_of([picks[i][n] for i, n in enumerate(lens)])
    assert [n for n, _doc in lm.statistics] == [
        "mxtpu_moe_assignments_held_total",
        "mxtpu_moe_experts_touched_total", "mxtpu_moe_routed_rows_total",
        "mxtpu_moe_layer_calls_total"]
    assert telemetry.snapshot()["gauges"]["mxtpu_moe_experts_held"] == count


def test_state_spec_names_rolling_windows_and_one_full_page(net):
    spec = net.state_spec(3, 40, "bfloat16")
    assert len(spec) == 10
    assert [(name, kind, shape[1]) for name, kind, shape, _dt
            in spec[:10:2]] \
        == [("layer0_k", "kv_window", 8), ("layer1_k", "kv_window", 8),
            ("layer2_k", "kv_window", 8), ("layer3_k", "kv_full", 40),
            ("layer4_k", "kv_window", 8)]
    assert all(shape == (3, shape[1], 2, 32) and dt == "bfloat16"
               for _n, _k, shape, dt in spec[:10])
    # a cache shorter than the window: the window buffer is the cache
    assert {shape[1] for _n, _k, shape, _dt
            in net.state_spec(1, 6, "float32")} == {6}
    with pytest.raises(mx.MXNetError, match="floating"):
        net.state_spec(1, 8, "int32")


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
