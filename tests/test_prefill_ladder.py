"""A bucket's ladder of prefill programs (docs/serving.md, "Buckets"):
an admission is prefilled at the shortest rung that holds its prompt,
not at the bucket's ``prompt_len``.

What is held here: the rule that makes a ladder (a function of the
buckets alone), that a prompt served through a short rung is the prompt
served through the full one, in a slot whose last tenant wrote past the
rung, for every served family; that a bucket's first admission makes
every rung ready, so no later one compiles; that the manifest and a
warm start carry the rungs; and the counters and the span argument that
say what prefill ran over.  ``tests/test_resize.py`` holds the rungs
across ``resize_slots``.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, telemetry
from mxnet_tpu.serving import BucketScheduler, Server, prefill_ladder
from mxnet_tpu.serving import server as server_mod

V = 61
TOL = 2e-5      # of the largest |logit|: the families' own (test_sambay.py)


@pytest.fixture(autouse=True)
def _clean_registry():
    server_mod._reset_registry()
    yield
    server_mod._reset_registry()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, V, n).astype("f4")


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("buckets,ladders", [
    ([(48, 256), (24, 1024)], [(256,), (512, 1024)]),       # Mistral's cells
    ([(96, 512)], [(256, 512)]),                            # phi4, Trinity
    ([(160, 1024)], [(256, 512, 1024)]),                    # Pangu
    ([(4, 32)], [(32,)]),                                   # every toy server
    ([(2, 511)], [(511,)]),
    ([(2, 768)], [(768,)]),         # 384 is no multiple of 256
    ([(2, 128), (2, 1024)], [(128,), (256, 512, 1024)]),
    ([(2, 512), (2, 1024)], [(256, 512), (1024,)]),
])
def test_the_ladder_is_a_function_of_the_buckets(buckets, ladders):
    sched = BucketScheduler(buckets, max_new_tokens=8, max_queue=4)
    assert [b.rungs for b in sched.buckets] == ladders
    assert [prefill_ladder(b.prompt_len, b.below)
            for b in sched.buckets] == ladders
    # a resize keeps the shape class, its ladder with it
    assert [b.resized(3).rungs for b in sched.buckets] == ladders


@pytest.mark.parametrize("prompt_len,bucket,rung", [
    (1, 256, 256), (128, 256, 256), (129, 256, 256), (256, 256, 256),
    (257, 1024, 512), (512, 1024, 512), (513, 1024, 1024),
    (1024, 1024, 1024)])
def test_a_prompt_takes_the_shortest_rung_that_holds_it(prompt_len, bucket,
                                                        rung):
    sched = BucketScheduler([(48, 256), (24, 1024)], 8, 4)
    b = sched.select_bucket(prompt_len)
    assert (b.prompt_len, b.rung_for(prompt_len)) == (bucket, rung)


# -- a short rung is the full rung --------------------------------------------

def _llama(**kwargs):
    from mxnet_tpu.models import LlamaForCausalLM, get_llama
    mx.random.seed(0)
    lm = LlamaForCausalLM(get_llama("llama_tiny", vocab_size=V, **kwargs))
    lm.initialize(mx.init.Xavier())
    return lm


def _sambay():
    # the published window, so that a rung of 256 fills half of the
    # rolling buffer and decode wraps it at 512
    from mxnet_tpu.models import SambaYForCausalLM, sambay_tiny
    mx.random.seed(0)
    lm = SambaYForCausalLM(sambay_tiny(vocab_size=V, sliding_window=512))
    lm.initialize(mx.init.Xavier())
    return lm


def _routed(cls, preset, **kwargs):
    mx.random.seed(2)
    lm = cls(preset(vocab_size=V, experts_held=(4, 8), **kwargs))
    lm.initialize(mx.init.Xavier())
    rng = np.random.RandomState(2)
    for name, p in lm.collect_params().items():
        if name.endswith("router_bias"):
            p.set_data(nd.array(0.3 * rng.randn(*p.shape).astype("f4")))
    return lm


def _afmoe():
    # a window between the rung and the bucket: the full rung leaves a
    # rolled buffer of 384, the short one 256 rows written in place
    from mxnet_tpu.models import AfmoeForCausalLM, afmoe_tiny
    return _routed(AfmoeForCausalLM, afmoe_tiny, sliding_window=384)


def _pangu():
    from mxnet_tpu.models import PanguMoeForCausalLM, pangu_moe_tiny
    return _routed(PanguMoeForCausalLM, pangu_moe_tiny)


def _first_logits(lm, prompt, length):
    """``lm.prefill`` of ``prompt`` right-padded to ``length``, into a
    state of that length: the first token's logits."""
    padded = np.zeros((1, length), "f4")
    padded[0, :len(prompt)] = prompt
    state = [nd.zeros(shape, dtype=dt)
             for _n, _k, shape, dt in lm.state_spec(1, length)]
    return lm.prefill(nd.array(padded), state,
                      last_pos=nd.array([len(prompt) - 1.0])).asnumpy()[0]


# family -> (builder, tokens after the first: SambaY decodes past position
# 512, where its rolling window wraps)
FAMILIES = {
    "llama": (_llama, 8),
    "llama_window": (lambda: _llama(sliding_window=320), 8),
    "sambay": (_sambay, 420),
    "afmoe": (_afmoe, 8),
    "pangu_moe": (_pangu, 8),
}


@pytest.mark.time_limit(300)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_prompt_through_rung_256_is_the_prompt_through_the_full_rung(
        family):
    """100 tokens into the ONE slot of a 512 bucket, after a 500-token
    tenant (so rows past the rung hold another request's values): first
    through the full rung (the ladder cut to it), then through rung 256.
    The same tokens, first and later; the same first-token logits; for a
    routed family the same counts and picks, call by call."""
    build, later = FAMILIES[family]
    lm = build()
    new = later + 1
    srv = Server(lm, buckets=[(1, 512)], max_new_tokens=new)
    bucket, = srv.sched.buckets
    assert bucket.rungs == (256, 512)
    tenant, prompt = _prompt(11, 500), _prompt(12, 100)
    calls = []
    if getattr(lm, "statistics", ()):
        srv.statistics_listener = lambda *call: calls.append(call)

    def served(rungs):
        bucket.rungs = rungs
        srv.submit(tenant, max_new_tokens=2)
        srv.run()
        del calls[:]
        positions = telemetry.counter(
            "mxtpu_serving_prefill_positions_total")
        before = positions.value
        req = srv.submit(prompt)
        srv.run()
        assert positions.value - before == rungs[0]     # the rung it took
        return req.generated, list(calls)

    full, full_calls = served((512,))
    short, short_calls = served((256, 512))
    assert len(short) == new and short == full
    assert sorted(srv._variants) == [
        "_b1x512_decode", "_b1x512_prefill", "_b1x512_prefill256"]

    want = _first_logits(lm, prompt, 512)
    got = _first_logits(lm, prompt, 256)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    assert int(np.argmax(want)) == short[0]

    names = [n for n, _doc in getattr(lm, "statistics", ())]
    assert len(short_calls) == len(full_calls) == (new if names else 0)
    for (kind, cols, counts, rows), (kind_f, cols_f, counts_f, rows_f) \
            in zip(short_calls, full_calls):
        assert (kind, cols) == (kind_f, cols_f)
        for name, c, c_f in zip(names, counts, counts_f):
            if kind == "prefill" and name == "mxtpu_mla_page_positions_total":
                # what the expanded attention ran over: the rung, a layer
                assert c * 2 == c_f and c % 256 == 0, (c, c_f)
            else:
                assert c == c_f, (kind, name, c, c_f)
        if kind == "prefill":
            # (positions, expert layers x k): the prompt's rows agree, a
            # padded row picked nothing
            rows, rows_f = rows.reshape(256, -1), rows_f.reshape(512, -1)
            assert (rows[:100] >= 0).all() and (rows[100:] == -1).all()
            assert (rows_f[100:] == -1).all()
            rows, rows_f = rows[:100], rows_f[:100]
        assert np.array_equal(rows, rows_f), kind


# -- warm-up is the server's -------------------------------------------------

@pytest.fixture(scope="module")
def net():
    return _llama()


def _one_prompt_a_rung(srv, seed=40):
    """A prompt for every rung of every bucket (its longest), served."""
    lens = [r for b in srv.sched.buckets for r in b.rungs]
    reqs = [srv.submit(_prompt(seed + i, n)) for i, n in enumerate(lens)]
    srv.run()
    return lens, reqs


@pytest.mark.time_limit(300)
def test_after_a_buckets_first_admission_no_rung_compiles(net):
    srv = Server(net, buckets=[(2, 256), (2, 1024)], max_new_tokens=3)
    # what a caller's warm-up sends: ONE prompt a bucket, of any length
    srv.generate([_prompt(1, 5), _prompt(2, 700)])
    assert {s for s in srv._warmed if "prefill" in s} == {
        "_b2x256_prefill", "_b2x1024_prefill", "_b2x1024_prefill512"}
    telemetry.clear_events()
    before = engine.cache_info()
    lens, reqs = _one_prompt_a_rung(srv)
    assert lens == [256, 512, 1024]
    after = engine.cache_info()
    assert after["misses"] == before["misses"]
    assert after["fresh_compiles"] == before["fresh_compiles"]
    for key, s in srv.stats()["buckets"].items():
        assert s["steady_misses"] == s["steady_fresh_compiles"] == 0, key
    assert telemetry.events("retrace") == []
    for r in reqs:
        want = net.generate(nd.array(r.prompt[None]), max_new_tokens=3)
        np.testing.assert_array_equal(r.tokens(), want.asnumpy()[0])


@pytest.mark.time_limit(300)
def test_a_manifest_and_a_warm_start_carry_every_rung(net, tmp_path,
                                                      monkeypatch):
    import json
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    engine.clear_cache()
    srv = Server(net, buckets=[(2, 1024)], max_new_tokens=3)
    cold = srv.generate([_prompt(3, 300)])      # rung 512 runs, two are made
    man = srv.save_signature(str(tmp_path / "serving.json"))
    rows = {v["suffix"]: v for v in json.load(open(man))["variants"]}
    assert {s: (v["kind"], v["k"]) for s, v in rows.items()} == {
        "_b2x1024_decode": ("decode", 0),
        "_b2x1024_prefill": ("prefill", 0),
        "_b2x1024_prefill256": ("prefill", 256),
        "_b2x1024_prefill512": ("prefill", 512)}
    # only the prompt's aval differs from rung to rung
    full = rows["_b2x1024_prefill"]["avals"]
    at = len(full) - 6
    for sfx, rung in (("", 1024), ("256", 256), ("512", 512)):
        avals = rows["_b2x1024_prefill" + sfx]["avals"]
        assert avals[at][0] == [1, rung]
        assert avals[:at] + avals[at + 1:] == full[:at] + full[at + 1:]

    # "fresh process": memory tier emptied, persistent tier kept
    engine.clear_cache()
    engine.reset_counters()
    srv2 = Server(net, buckets=[(2, 1024)], max_new_tokens=3)
    assert srv2.warm_start(man)
    np.testing.assert_array_equal(srv2.generate([_prompt(3, 300)])[0],
                                  cold[0])
    lens, _reqs = _one_prompt_a_rung(srv2)
    assert lens == [256, 512, 1024]
    assert engine.cache_info()["fresh_compiles"] == 0
    st = srv2.stats()["buckets"]["2x1024"]
    assert st["steady_dispatches"] > 0
    assert st["steady_misses"] == st["steady_fresh_compiles"] == 0


# -- what prefill ran over ----------------------------------------------------

def test_counters_and_span_say_what_prefill_ran_over(net):
    from mxnet_tpu import profiler
    srv = Server(net, buckets=[(2, 512)], max_new_tokens=2)
    srv.generate([_prompt(5, 9)])               # compiles
    tokens = telemetry.counter("mxtpu_serving_prompt_tokens_total")
    positions = telemetry.counter("mxtpu_serving_prefill_positions_total")
    t0, p0 = tokens.value, positions.value
    reqs = [srv.submit(_prompt(6, 100)), srv.submit(_prompt(7, 257))]
    profiler.set_state("run")
    try:
        srv.run()
    finally:
        profiler.set_state("stop")
    with profiler._lock:
        admits = [e["args"] for e in profiler._events
                  if e["ph"] == "X" and e["name"] == "mxtpu.serving.admit"]
        profiler._events.clear()
    assert (tokens.value - t0, positions.value - p0) == (357, 256 + 512)
    # an admission's two spans (enqueue, read) name its rung
    assert sorted((a["req"], a["bucket"], a["rung"]) for a in admits) == \
        sorted([(reqs[0].id, 512, 256), (reqs[1].id, 512, 512)] * 2)


def test_a_failed_read_names_the_rungs_program(net):
    """``_Owed.k`` counts a dispatch's steps; the program's name comes
    from its variant."""
    from mxnet_tpu.base import MXNetError
    srv = Server(net, buckets=[(1, 512)], max_new_tokens=2)
    srv.generate([_prompt(8, 4)])

    class Dead:
        def __array__(self, *a, **k):
            raise RuntimeError("device lost")

    telemetry.clear_events()
    srv.submit(_prompt(9, 50))
    # a round's first half, by hand: the prefill is enqueued, not yet read
    (bucket, slot, req), = srv.sched.admissions()
    srv._admit(bucket, slot, req)
    rec, = srv._owed
    assert (rec.kind, rec.k, rec.variant) == ("prefill", 1, 256)
    rec.out = Dead()
    with pytest.raises(MXNetError, match="recover"):
        srv.settle()
    poison, = telemetry.events("poison")
    assert poison["name"] == srv.name + "_b1x512_prefill256"
    srv.recover()
