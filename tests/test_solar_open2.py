"""Solar Open 2 (gated delta-rule linear attention + gated NoPE GQA +
routed experts) against its plain reference
(``models/solar_open2_reference.py``), at ``solar_open2_tiny`` on the CPU,
float32, seeded weights: two periods of four layers (GQA on 0 and 4, the
delta rule on the rest), 4 linear-attention heads of 8, 4 query and 2
key-value heads of 16 over a hidden size of 64, gates of rank 8, 16
experts top-4.

TOLERANCE.  Program and reference compute the same float32 mathematics
in another order: the program scans a prompt in chunks (a triangular
solve a chunk) and then steps the state one token at a time, the
reference runs the per-token recurrence over the whole sequence; the
program sorts and groups the held experts' products, the reference loops
over experts.  They differ by float32 rounding carried through 8 layers:
at most 6.3e-6 of the largest |logit| was read here, 1.0e-5 on a net of
the initial weights.  The limit is ``TOL`` = 5e-5, five times the larger,
and far under what each control gives (``CONTROLS``, each at float32
otherwise: the nearest, a bfloat16 state, read 1.5e-2; ``b = sigmoid``
0.47, no correction 0.99): room on both sides.

ROUTING IS DISCRETE (``tests/test_afmoe.py`` says why): the seeded
weights leave a margin of at least ``MARGIN`` between the 4th and 5th
score of every row these tests route; ``_reference`` asserts it.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import SolarOpen2ForCausalLM, solar_open2_tiny
from mxnet_tpu.models import solar_open2_reference as ref
from mxnet_tpu.serving import KVCachePool, Server

V = 256
TOL = 5e-5
MARGIN = 2e-6
LAYERS, GQA = 8, (0, 4)
HD, DK = 4, 8                    # linear-attention heads, their width
CONV = 3 * HD * DK               # channels of the short convolution


def _build(seed=0, **kwargs):
    mx.random.seed(seed)
    lm = SolarOpen2ForCausalLM(solar_open2_tiny(vocab_size=V, **kwargs))
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
    lm = SolarOpen2ForCausalLM(solar_open2_tiny(vocab_size=V,
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


S = 40      # every reference runs at this length: its pieces compile once


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


def test_the_presets_are_whole_periods_of_one_gqa_and_three_delta_layers():
    m = solar_open2_tiny(vocab_size=V)
    assert [l.kind for l in m.layers] == ["full", "delta", "delta",
                                          "delta"] * 2
    assert m.experts_held == (0, 16) and m.delta_shape == (HD, DK, DK)
    # pre-norm layers, no q/k norm, no selection bias
    names = list(m.collect_params())
    assert not [n for n in names if "ln2_" in n or "ln4_" in n
                or "qnorm" in n or "knorm" in n or n.endswith("router_bias")]
    ep8 = mx.models.get_solar_open2("solar_open2_ep8")
    assert [l.kind for l in ep8.layers] == ["full", "delta", "delta", "delta"]
    assert ep8.experts_held == (0, 40) and ep8.num_experts == 320
    with pytest.raises(mx.MXNetError, match="experts_held"):
        solar_open2_tiny(vocab_size=V, experts_held=(12, 8))
    with pytest.raises(mx.MXNetError, match="gqa_layers"):
        solar_open2_tiny(vocab_size=V, gqa_layers=(0, 8))
    with pytest.raises(mx.MXNetError, match="unknown solar_open2"):
        mx.models.get_solar_open2("solar_open2_huge")


def test_full_forward_matches_the_reference(net):
    toks = _tokens(1, S)
    want, _ = _reference(net, toks)
    _close(net(nd.array(toks[None])).asnumpy()[0], want, "full forward")


@pytest.mark.parametrize("stale", ["zeros", "an_evicted_requests_state"])
def test_prefill_then_decode_rows_at_their_own_offsets(net, stale):
    """The call shapes the server's programs make: a right-padded batch
    prefilled at each row's ``last_pos`` (the chunked scan), then one
    token a row (the one-token update) at (B,) offsets, against the
    reference's one full forward.  A pool an evicted request left full
    reads the same: prefill starts every recurrent state and conv tail
    from the prompt alone, and K,V rows past a slot's offset are never
    attended."""
    lens, new = (6, 14), 18
    seqs = [_tokens(10 + i, n + new) for i, n in enumerate(lens)]
    wants = [_reference(net, s)[0] for s in seqs]
    state = net.init_cache(2, max(lens) + new)
    if stale != "zeros":
        rng = np.random.RandomState(5)
        for buf in state:
            buf[:] = nd.array(30.0 * rng.randn(*buf.shape).astype("f4"))
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


def test_served_tokens_are_the_references_argmax_and_generates(net):
    """Through ``serving.Server``: four prompts of different lengths over
    a two-slot bucket, so two of them take a slot whose recurrent state
    and conv tail an evicted request left behind; every served token is
    the argmax of the reference's full forward of what came before, and
    what ``generate`` gives."""
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
    assert pool.bytes_by_kind() == {
        "kv_full": 2 * 2 * 2 * 32 * 2 * 16 * 4,
        "conv": 6 * 2 * 3 * CONV * 4, "ssm": 6 * 2 * HD * DK * DK * 4}


def test_a_served_requests_matrix_states_are_the_references(net):
    """The state a request leaves in its slot after its last decode (the
    chunked scan over its prompt, then one update a generated token but
    the last) is the reference's after the same tokens, in every
    linear-attention layer; a reference whose state is rounded to
    bfloat16 token by token is not (what the benchmark's comparison holds
    a served probe to)."""
    srv = Server(net, buckets=[(2, 16)], max_new_tokens=16)
    req = srv.submit(_tokens(30, 13), max_new_tokens=11)
    srv.run()
    toks = req.tokens()[:-1]
    pool, = srv._pools.values()
    served = {int(name[len("layer"):-len("_state")]): np.asarray(buf[0])
              for (name, kind, _s, _d), buf in zip(pool.spec, pool.flat())
              if kind == "ssm"}
    assert sorted(served) == [i for i in range(LAYERS) if i not in GQA]
    cfg, held = ref.config_of(net)
    for precision, close in (("float32", True), ("state_bfloat16", False)):
        states = {}
        ref.forward_logits(ref.weights_of(net), toks, cfg, precision, held,
                           states=states)
        gaps = [np.linalg.norm(served[l] - states[l])
                / np.linalg.norm(states[l]) for l in sorted(served)]
        assert (max(gaps) <= TOL) == close, (precision, gaps)


def test_the_shares_add_up_to_the_uncut_layer(net):
    """Eight shares of 2 experts: their routed partial sums, with what
    every chip computes alike (the shared expert) counted once, are the
    uncut reference's layer output."""
    rng = np.random.RandomState(3)
    m = nd.array(rng.randn(1, S, 64).astype("f4"))
    whole = net.model.layers[1].ffn
    shared = whole.shared(m).asnumpy()
    parts, counted = 0, 0
    for first in range(0, 16, 2):
        share = _share(net, first, 2).model.layers[1].ffn
        out, (held, _touched), _sel = share.route(m)
        parts = parts + out.asnumpy() - shared
        counted += int(held.asnumpy())
    assert counted == S * 4             # every assignment lands once
    cfg, _ = ref.config_of(net)
    w = {k[len("layer1_"):]: v for k, v in ref.weights_of(net).items()
         if k.startswith("layer1_")}
    routed, _pick, margin = ref._routed(m.asnumpy()[0], w, cfg, (0, 16),
                                        None, "float32")
    assert float(margin.min()) > MARGIN
    want = np.asarray(routed) + np.asarray(ref._shared(
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


CONTROLS = {"beta_sigmoid": dict(beta=1.0),
            "no_correction": dict(correction=False),
            "state_bfloat16": dict(state=ref.BFLOAT16)}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_comparison(net, monkeypatch, control):
    """A reference with ``b = sigmoid`` (no negative eigenvalues), without
    the ``- b k k^T S'`` correction (plain gated linear attention), or
    with a bfloat16 state, all else float32, is refused by ``TOL``: the
    comparison can tell each from the program.  The same controls at the
    stated precision are the builder's (``PRECISIONS``)."""
    # a name of its own: the reference's jitted pieces keep a trace by it
    name = f"float32_{control}"
    monkeypatch.setitem(ref.PRECISIONS, name, dict(
        ref.PRECISIONS["float32"], **CONTROLS[control]))
    toks = _tokens(6, S)
    want, routing = _reference(net, toks)
    wrong, _ = _reference(net, toks, name, selections=routing["picked"])
    assert _err(wrong, want) > 10 * TOL
    assert ref.PRECISIONS[control] == dict(ref.PRECISIONS["stated"],
                                           **CONTROLS[control])


def test_statistics_count_the_linear_layers_and_their_state_rows(net):
    """After a padded prefill and after a decode step ``last_statistics``
    holds, after the expert layers' four counts, the state rows the
    linear-attention layers wrote (one a row of the batch a layer) and
    their calls; the counts ride out to the server's listener."""
    lens = (6, 13)
    state = net.init_cache(2, 24)
    prompt = np.zeros((2, 16), "f4")
    for i, n in enumerate(lens):
        prompt[i, :n] = _tokens(30 + i, n)
    net.prefill(nd.array(prompt), state, nd.array(np.array(lens, "f4") - 1))
    got = [int(c.asnumpy()) for c in net.last_statistics[:6]]
    assert got[2:] == [LAYERS * sum(lens), LAYERS, 6 * 2, 6]
    net.decode_step(nd.array([[3.0], [4.0]]), state,
                    nd.array(np.array(lens, "f4")))
    got = [int(c.asnumpy()) for c in net.last_statistics[:6]]
    assert got[2:] == [LAYERS * 2, LAYERS, 6 * 2, 6]
    assert [n for n, _doc in net.statistics][4:] == [
        "mxtpu_delta_state_rows_total", "mxtpu_delta_layer_calls_total"]

    from mxnet_tpu import telemetry
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=4)
    heard = []
    srv.statistics_listener = lambda kind, cols, counts, rows: heard.append(
        (kind, len(cols), [int(c) for c in counts[4:]]))
    before = telemetry.snapshot()["counters"].get(
        "mxtpu_delta_state_rows_total", 0)
    srv.submit(_tokens(40, 5))
    srv.run()
    # one prefill of the lone row; three decodes move BOTH slots' states
    assert heard == [("prefill", 1, [6, 6])] + [("decode", 1, [12, 6])] * 3
    assert telemetry.snapshot()["counters"][
        "mxtpu_delta_state_rows_total"] - before == 6 + 3 * 12


def test_state_spec_holds_kv_pages_conv_tails_and_float32_matrix_states(
        net):
    spec = net.state_spec(3, 40, "bfloat16")
    want = []
    for i in range(LAYERS):
        if i in GQA:
            want += [(f"layer{i}_{x}", "kv_full", (3, 40, 2, 16), "bfloat16")
                     for x in "kv"]
        else:
            want += [(f"layer{i}_conv", "conv", (3, 3, CONV), "bfloat16"),
                     (f"layer{i}_state", "ssm", (3, HD, DK, DK), "float32")]
    assert spec == want
    pool = KVCachePool(net, slots=3, cache_len=40, dtype="bfloat16")
    # the matrix state does not grow with the request
    assert pool.bytes_by_kind() == {"kv_full": 2 * 2 * 3 * 40 * 2 * 16 * 2,
                                    "conv": 6 * 3 * 3 * CONV * 2,
                                    "ssm": 6 * 3 * HD * DK * DK * 4}
    with pytest.raises(mx.MXNetError, match="floating"):
        net.state_spec(1, 8, "int32")


def test_served_in_bfloat16_the_state_stays_float32():
    lm = _build(1)
    lm.cast("bfloat16")
    srv = Server(lm, buckets=[(2, 16)], max_new_tokens=6,
                 cache_dtype="bfloat16")
    reqs = [srv.submit(_tokens(50 + i, n), max_new_tokens=6)
            for i, n in enumerate((9, 3))]
    srv.run()
    assert all(len(r.generated) == 6 for r in reqs)
    pool, = srv._pools.values()
    dtypes = {kind: str(b.dtype) for (_n, kind, _s, _d), b
              in zip(pool.spec, pool.buffers())}
    assert dtypes == {"kv_full": "bfloat16", "conv": "bfloat16",
                      "ssm": "float32"}


def test_each_precision_rounds_more_than_the_one_above(net):
    toks = _tokens(9, S)
    cfg, held = ref.config_of(net)
    exact, stated, float8 = (
        ref.forward_logits(ref.weights_of(net), toks, cfg, p, held)
        for p in ("float32", "stated", "float8"))
    assert TOL < _err(stated, exact) < _err(float8, exact)
    with pytest.raises(KeyError):
        ref.forward_logits(ref.weights_of(net), toks, cfg, "float16", held)


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    src = inspect.getsource(ref)
    code = src[src.index('"""', 3) + 3:src.index("def weights_of")]
    assert "mxnet_tpu" not in code and "import" in code
    assert "delta_rule" not in code and "triangular" not in code
