"""The serving plane over a THIRD model (docs/serving.md, "State kinds"
and "Model statistics"): ``models/afmoe.py`` holds a share of its routed
experts, keeps ``kv_window`` and ``kv_full`` pages side by side with no
recurrent state, and counts in every program what its expert layers did.

The contracts of ``tests/test_serving.py`` that hold "for both state
planes" (its ``lm_bucket`` tests: the resident RNG key, one decode ahead
of the reads, evict / deadline / resize / ``save_signature`` / drain /
poison -> ``recover`` with a decode outstanding) are run here for the
third, as the SAME test functions over this file's ``lm_bucket``; the two
longest of them (greedy parity with mid-stream admissions, the dispatch
count over a run with a drain: a minute of eager reference each on the
CPU) are held for this model by ``tests/test_afmoe.py`` and by the
statistics tests below.  A file of its own so that the suite's workers
share the load.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, telemetry
from mxnet_tpu.serving import KVCachePool, Server

from test_serving import (  # noqa: F401  (fixtures and shared contracts)
    V, _ahead, _clean_registry, _prompt, net,
    test_one_host_key_a_server,
    test_keys_differ_by_dispatch_and_by_row,
    test_reseed_takes_effect_at_the_next_dispatch,
    test_mixed_batch_greedy_exact_sampled_seeded,
    test_warm_start_key_input_round_trip_and_old_manifest,
    test_next_decode_is_enqueued_before_the_last_is_read,
    test_eos_overrun_is_dropped_and_leaves_no_trace,
    test_evict_and_requeue_with_a_decode_outstanding,
    test_deadline_expiry_with_a_decode_outstanding,
    test_resize_and_save_signature_drain_the_owed_reads,
    test_poison_recover_with_a_decode_outstanding,
    test_a_failure_at_the_late_read_poisons_and_names_the_dispatch,
    test_preemption_drain_reads_what_is_owed)

STATS = ("mxtpu_moe_assignments_held_total",
         "mxtpu_moe_experts_touched_total", "mxtpu_moe_routed_rows_total",
         "mxtpu_moe_layer_calls_total")


@pytest.fixture(scope="module")
def routed():
    from mxnet_tpu.models import AfmoeForCausalLM, afmoe_tiny
    mx.random.seed(2)
    np.random.seed(2)
    lm = AfmoeForCausalLM(afmoe_tiny(vocab_size=V, experts_held=(4, 8)))
    lm.initialize(mx.init.Xavier())
    rng = np.random.RandomState(2)
    for name, p in lm.collect_params().items():
        if name.endswith("router_bias"):
            p.set_data(nd.array(0.3 * rng.randn(*p.shape).astype("f4")))
    return lm


@pytest.fixture
def lm_bucket(routed):
    """The routed decoder and a two-slot bucket that fits the prompts of
    the shared contracts."""
    return routed, (2, 16)


def _stats():
    return [telemetry.counter(n).value for n in STATS]


def test_routed_pool_mixes_window_and_full_pages(routed):
    pool = KVCachePool(routed, slots=3, cache_len=20, dtype="bfloat16")
    assert pool.num_buffers - 1 == len(routed.state_spec(3, 20)) == 10
    assert [(k, s[1]) for _n, k, s, _d in pool.spec[::2]] == [
        ("kv_window", 8)] * 3 + [("kv_full", 20), ("kv_window", 8)]
    assert pool.bytes_by_kind() == {
        "kv_window": 4 * 2 * 3 * 8 * 2 * 32 * 2,
        "kv_full": 2 * 3 * 20 * 2 * 32 * 2}


@pytest.mark.parametrize("decode_steps", [1, 2])
def test_the_served_picks_reach_a_listener_call_by_call(routed,
                                                        decode_steps):
    """What a model leaves behind its counts goes to the server's
    ``statistics_listener`` with the call's kind and the columns that
    belonged to a request: a request's routing can be put together from
    it, position by position, prefill (-1 on the padded rows) and decode
    alike, and an idle slot's row is told from a served one's."""
    from mxnet_tpu.models import afmoe_reference as ref
    srv = Server(routed, buckets=[(2, 16)], max_new_tokens=5)
    calls = []
    srv.statistics_listener = lambda *call: calls.append(call)
    cfg, held = ref.config_of(routed)
    for seed, n in ((84, 14), (85, 5)):
        del calls[:]
        req = srv.submit(_prompt(seed, n))
        srv.run(decode_steps=decode_steps)
        toks = req.tokens()
        routing = {}
        padded = np.concatenate([toks[:-1], np.ones(20 - len(toks), "f4")])
        ref.forward_logits(ref.weights_of(routed), padded, cfg, "float32",
                           held, routing=routing)
        (kind, columns, counts, rows), decodes = calls[0], calls[1:]
        assert kind == "prefill" and columns == [0] and len(counts) == 4
        rows = rows.reshape(16, 4, 4)
        assert (rows[:n] == routing["picked"][:n]).all()
        assert (rows[n:] == -1).all()
        got = []
        for kind, columns, counts, rows in decodes:
            assert kind == "decode" and len(columns) == 1
            assert counts[3] == 4 * decode_steps    # expert-layer calls
            got += list(rows.reshape(decode_steps, 2, 4, 4)[:, columns[0]])
        m = len(toks) - 1 - n
        assert (np.stack(got)[:m] == routing["picked"][n:n + m]).all()


@pytest.mark.parametrize("decode_steps", [1, 3])
def test_model_statistics_ride_with_the_tokens(routed, decode_steps):
    """The counters rise by a NumPy count of the plain reference's picks:
    two requests fill both slots from the first round to the last, so
    every decode routes exactly their two rows; nothing but the token
    read brings the counts back (the dispatch count is what it is for
    any model, every decode but the first runs ahead)."""
    from mxnet_tpu.models import afmoe_reference as ref
    new = 7
    prompts = [_prompt(80, 6), _prompt(81, 13)]
    srv = Server(routed, buckets=[(2, 16)], max_new_tokens=new)
    srv.generate(prompts, decode_steps=decode_steps)    # compile
    c0, a0, d0 = _stats(), _ahead(), engine.dispatch_count()
    outs = srv.generate(prompts, decode_steps=decode_steps)
    calls = 2 + (new - 1) // decode_steps               # prefills, decodes
    assert engine.dispatch_count() - d0 == calls
    assert _ahead() - a0 == calls - 2 - 1
    cfg, (first, count) = ref.config_of(routed)
    picks = []
    for out in outs:
        routing = {}
        # causal: padded to one length, the reference compiles once
        padded = np.concatenate([out[:-1], np.ones(20 - len(out), "f4")])
        ref.forward_logits(ref.weights_of(routed), padded, cfg,
                           "float32", (first, count), routing=routing)
        assert routing["margin"][:len(out) - 1].min() > 2e-6  # none flips
        picks.append(routing["picked"])             # (S, 4 layers, k)

    def count_of(rows):
        rows = np.stack(rows)
        held = (rows >= first) & (rows < first + count)
        return [held.sum(), sum(len(np.unique(rows[:, l][held[:, l]]))
                                for l in range(4)), rows.shape[0] * 4, 4]

    want = np.zeros(4, int)
    for p, pick in zip(prompts, picks):                 # one prefill each
        want += count_of(list(pick[:len(p)]))
    for step in range(new - 1):                         # both rows a step
        want += count_of([pick[len(p) + step]
                          for p, pick in zip(prompts, picks)])
    assert [int(b - a) for a, b in zip(c0, _stats())] == list(want)


def test_a_model_without_statistics_hands_out_tokens_alone(net, routed):
    """The hook is the model's to use: a Llama program's first output is
    its tokens as before, a routed model's is longer by its four counts
    and its rows' picks, and the two are different programs by the
    structural hash."""
    plain = Server(net, buckets=[(2, 8)], max_new_tokens=2)
    assert plain._stat_rows == ()
    plain.submit(_prompt(82, 3))
    plain.step()
    assert plain._owed[-1].out.shape == (2,)
    counting = Server(routed, buckets=[(2, 16)], max_new_tokens=2)
    assert [n for n, _doc in counting._stat_rows] == list(STATS)
    counting.submit(_prompt(83, 3))
    counting.step()
    assert counting._owed[-1].out.shape == (2 + 4 + 2 * 16,)
    counting.run()
    plain.run()
    h = counting._struct_hash
    counting._stat_rows = ()
    assert counting._compute_struct_hash() != h
