"""Serving-plane tests (docs/serving.md): continuous batching over
fixed buckets with donated KV-cache pages.

The contracts under test are the ISSUE 9 acceptance criteria: steady-
state decode is ONE engine dispatch per step with ZERO retraces across
admits/evicts (asserted via ``engine.cache_info()``), an evicted
slot's garbage K/V never leaks into a live request's logits
(bit-parity), and a fresh process serves its first token with 0 fresh
compiles after ``Server.warm_start`` (the PR 5 acceptance counter).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import faults
from mxnet_tpu.models import LlamaForCausalLM, llama_tiny
from mxnet_tpu.serving import (BucketScheduler, KVCachePool, Request,
                               Server)
from mxnet_tpu.serving import server as server_mod

V = 61


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    np.random.seed(0)
    lm = LlamaForCausalLM(llama_tiny(vocab_size=V))
    lm.initialize(mx.init.Xavier())
    return lm


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, V, n).astype("f4")


@pytest.fixture(autouse=True)
def _clean_registry():
    server_mod._reset_registry()
    yield
    server_mod._reset_registry()


# -- scheduler core (host logic, no dispatches) ------------------------------

def test_bucket_selection():
    """A request lands in the SMALLEST bucket holding its prompt."""
    s = BucketScheduler([(2, 32), (2, 8)], max_new_tokens=4,
                        max_queue=8)
    assert [b.prompt_len for b in s.buckets] == [8, 32]
    assert s.select_bucket(3).prompt_len == 8
    assert s.select_bucket(8).prompt_len == 8
    assert s.select_bucket(9).prompt_len == 32
    assert s.select_bucket(33) is None
    with pytest.raises(MXNetError, match="largest bucket"):
        s.enqueue(Request(np.zeros(40), 4))


def test_admit_evict_finish_matrix():
    """Slot lifecycle: fill every slot, block the overflow in the
    queue, free slots by finish AND evict, watch FIFO admission refill
    them — shapes never change, only slot contents."""
    s = BucketScheduler([(2, 8)], max_new_tokens=4, max_queue=8)
    reqs = [Request(np.ones(4), 4) for _ in range(5)]
    for r in reqs:
        s.enqueue(r)
    adm = s.admissions()
    assert [r.id for _, _, r in adm] == [reqs[0].id, reqs[1].id]
    assert s.queue_depth() == 3
    assert s.buckets[0].n_active() == 2
    assert s.admissions() == []          # bucket full: queue holds
    # finish one, evict the other
    s.finish(reqs[0])
    s.evict(reqs[1], reason="test")
    assert reqs[1].state == "evicted"
    adm2 = s.admissions()
    assert [r.id for _, _, r in adm2] == [reqs[2].id, reqs[3].id]
    # a requeued eviction restarts from its prompt
    reqs[2].generated = [5]
    s.evict(reqs[2], reason="preempt", requeue=True)
    assert reqs[2].state == "queued" and reqs[2].generated == []
    # release rewinds the slot's offset/mask
    b = s.buckets[0]
    free = [j for j, r in enumerate(b.requests) if r is None]
    assert all(b.active[j] == 0 and b.offsets[j] == 0 for j in free)


def test_queue_bound():
    s = BucketScheduler([(1, 8)], max_new_tokens=4, max_queue=2)
    s.enqueue(Request(np.ones(4), 4))
    s.enqueue(Request(np.ones(4), 4))
    with pytest.raises(MXNetError, match="queue full"):
        s.enqueue(Request(np.ones(4), 4))


def test_kvcache_pool_contract(net):
    pool = KVCachePool(net, slots=2, cache_len=8)
    flat = pool.flat()
    # K and V a layer, then the slots' last tokens (the pool's own)
    assert len(flat) == 2 * len(net.model.layers) + 1 == pool.num_buffers
    assert flat[0].shape == (2, 8, 2, 16)    # tiny GQA: 2 kv heads, d 16
    assert flat[-1].shape == (2, 1) and str(flat[-1].dtype) == "float32"
    assert len(pool.spec) == len(flat) - 1   # no row of the model's spec
    with pytest.raises(MXNetError, match="adopt"):
        pool.adopt(flat[:1])
    pool.poison("boom")
    assert pool.poisoned
    pool.reset()
    assert pool.poisoned is None


# -- serving correctness ------------------------------------------------------

def test_greedy_parity_with_generate(net):
    """Continuously batched greedy decode must reproduce the reference
    single-request generate() path token-for-token, across different
    prompt lengths sharing one bucket."""
    prompts = [_prompt(0, 5), _prompt(1, 8), _prompt(2, 2)]
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=6)
    outs = srv.generate(prompts)
    for p, out in zip(prompts, outs):
        ref = net.generate(nd.array(p[None]),
                           max_new_tokens=6).asnumpy()[0]
        np.testing.assert_array_equal(out, ref)


def test_evicted_slot_garbage_never_leaks(net):
    """Bit-parity: a request decoded next to an evicted neighbor's
    garbage K/V produces EXACTLY the tokens it produces next to a
    zeroed slot — per-row attention independence, end to end."""
    pa, pb = _prompt(3, 6), _prompt(4, 7)
    solo = Server(net, buckets=[(2, 8)], max_new_tokens=6)
    ref = solo.generate([pa])[0]

    srv = Server(net, buckets=[(2, 8)], max_new_tokens=6)
    ra = srv.submit(pa)
    rb = srv.submit(pb)
    srv.step()                       # both admitted, one decode step
    srv.evict(rb, reason="preempt")  # slot 1 now holds garbage K/V
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), ref)


def test_model_level_row_isolation(net):
    """The structural half of the guarantee: per-slot decode logits
    are BITWISE independent of the other rows' cache contents."""
    toks = nd.array(_prompt(5, 2)[:2].reshape(2, 1))
    # both rows mid-sequence: row 1's VISIBLE positions 0..2 differ
    # between the two cache sets, row 0's are identical
    off = nd.array(np.array([3.0, 3.0], "f4"))
    rng = np.random.RandomState(0)
    base = net.init_cache(2, 8)
    c_zero, c_garb = [], []
    for page in base:                           # flat: k0, v0, k1, v1
        z = page.asnumpy().copy()
        z[0] = rng.randn(*z[0].shape)           # row 0: shared history
        g = z.copy()
        g[1] = rng.randn(*g[1].shape) * 1e3     # row 1: garbage
        c_zero.append(nd.array(z))
        c_garb.append(nd.array(g))
    l_zero = net.decode_step(toks, c_zero, off).asnumpy()
    l_garb = net.decode_step(toks, c_garb, off).asnumpy()
    np.testing.assert_array_equal(l_zero[0], l_garb[0])
    assert np.abs(l_zero[1] - l_garb[1]).max() > 0   # sanity: row 1 DID change


def _write_rows_by_hand(cache, new, off):
    """``_cache_update`` with a (B,) offset as a plain loop: each row
    at its own position, brought into the page as
    ``lax.dynamic_update_slice`` brings it (below zero counts from the
    end, then clamped), cast on store (both arrays float32 here; the
    caller rounds)."""
    out = cache.copy()
    c, n = cache.shape[1], new.shape[1]
    for b in range(cache.shape[0]):
        o = int(off[b])
        o = int(np.clip(o + c if o < 0 else o, 0, c - n))
        out[b, o:o + n] = new[b]
    return out


# name -> (page dtype, offsets (B = 4, C = 8), positions a row, how)
_ROW_WRITES = {
    "each_its_own": ("float32", [0, 7, 3, 4], 1, "op"),
    "below_zero_and_past_the_page": ("float32", [-1, 8, -300, 4000], 1,
                                     "op"),
    "every_row_the_same": ("float32", [5, 5, 5, 5], 1, "op"),
    "float_offsets_mod_len": ("float32", [9.0 % 8.0, 7.0, 16.0 % 8.0,
                                          3.0], 1, "op"),
    "float32_into_bfloat16": ("bfloat16", [2, 0, 7, 7], 1, "op"),
    "two_positions_a_row": ("float32", [0, 6, 7, 3], 2, "op"),
    "two_positions_bfloat16": ("bfloat16", [1, -2, 9, 3], 2, "op"),
    "through_nd": ("float32", [1, 2, 6, 0], 1, "nd"),
    "inside_a_scan": ("float32", [0, 5, 2, 7], 1, "scan"),
    "inside_a_scan_bfloat16": ("bfloat16", [6, 1, 1, 3], 1, "scan"),
    "grad": ("float32", [3, 3, 0, 7], 1, "grad"),
    "lane_block_kernel": ("bfloat16", [0, 255, 128, 127], 1, "kernel"),
    "lane_block_kernel_ragged_page": ("bfloat16", [199, 128, 0, 150], 1,
                                      "kernel"),
    "lane_block_routing": ("bfloat16", [130, 2, 255, 0], 1, "routed"),
    "lane_block_kernel_dp4": ("bfloat16", [64, 1, 200, 255], 1, "sharded"),
    "lane_block_grad": ("float32", [77, 0, 255, 128], 1, "grad_routed"),
}


@pytest.mark.parametrize("name", sorted(_ROW_WRITES))
def test_cache_update_per_row_matches_a_loop(name, monkeypatch):
    """``_cache_update`` with a (B,) offset is ONE in-place write a
    page (``ops/page_write.py``), not a loop over the rows; whatever
    the spelling, the result is the loop's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import page_write
    from mxnet_tpu.ops.tensor import _cache_update
    dtype, off, n, how = _ROW_WRITES[name]
    # the lane-block kernel moves 128-lane blocks of positions; a page
    # of 200 ends in a partial one
    c = 8 if not name.startswith("lane_block") else \
        200 if "ragged" in name else 256
    rng = np.random.RandomState(len(name))
    shape = (4, c, 2, 16)

    def rounded(a):
        return np.asarray(jnp.asarray(a).astype(dtype).astype("float32"))

    cache = rounded(rng.randn(*shape).astype("f4"))
    new = rng.randn(4, n, 2, 16).astype("f4")
    off = np.asarray(off, "f4" if isinstance(off[0], float) else "i4")
    page = jnp.asarray(cache).astype(dtype)
    want = _write_rows_by_hand(cache, rounded(new), off)
    if how == "op":
        got = _cache_update(page, jnp.asarray(new), jnp.asarray(off))
    elif how == "nd":
        buf = nd.array(cache)
        nd._cache_update(buf, nd.array(new), offset=nd.array(off),
                         out=buf)
        got = buf.asnumpy()
    elif how == "scan":
        # K steps, every row one position on a step, wrapping
        def body(pg, i):
            return _cache_update(pg, jnp.asarray(new) + i,
                                 (jnp.asarray(off) + i) % c), None
        got, _ = jax.lax.scan(body, page, jnp.arange(3))
        want = cache
        for i in range(3):
            want = _write_rows_by_hand(want, rounded(new + i),
                                       (off + i) % c)
    elif how in ("grad", "grad_routed"):
        if how == "grad_routed":
            monkeypatch.setattr(page_write, "_positions_on_lanes",
                                lambda shape, dtype: True)

        def loss(pg, nw):
            return jnp.sum(_cache_update(pg, nw, jnp.asarray(off))
                           * jnp.asarray(cache))
        g_page, g_new = jax.grad(loss, argnums=(0, 1))(
            page, jnp.asarray(new))
        # a written row's old value has no say; the new value's
        # cotangent is the weight that sits where it landed
        hole = _write_rows_by_hand(cache, np.zeros_like(new), off)
        np.testing.assert_array_equal(np.asarray(g_page), hole)
        np.testing.assert_array_equal(
            np.asarray(g_new)[:, 0],
            cache[np.arange(4), off.astype(int)])
        return
    elif how == "kernel":
        monkeypatch.setattr(page_write, "_INTERPRET", True)
        got = page_write._lane_block_call(
            page, jnp.asarray(new)[:, 0].astype(dtype), jnp.asarray(off))
    elif how == "sharded":
        # under a dp plan each shard's kernel writes its own rows: the
        # partitioner is told so and gathers nothing
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        monkeypatch.setattr(page_write, "_INTERPRET", True)
        by_rows = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("dp",)),
                                PartitionSpec("dp"))
        args = [jax.device_put(a, by_rows) for a in (
            page, jnp.asarray(new)[:, 0].astype(dtype), jnp.asarray(off))]
        fn = jax.jit(page_write._lane_block_rows, out_shardings=by_rows)
        text = fn.lower(*args).compile().as_text()
        assert "all-gather" not in text and "all-reduce" not in text
        got = fn(*args)
    else:
        # a page the TPU would store positions-minor takes the kernel
        # on the TPU and the scatter anywhere else: same program text
        monkeypatch.setattr(page_write, "_positions_on_lanes",
                            lambda shape, dtype: True)
        got = jax.jit(_cache_update)(page, jnp.asarray(new),
                                     jnp.asarray(off))
    assert str(got.dtype) == dtype
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(got).astype("float32")), want)


def test_sampling_seeded_and_in_range(net):
    """Temperature/top-k sampling threads the fold_in scheme off the
    global stream: same seed -> same tokens; all tokens valid."""
    prompts = [_prompt(6, 4), _prompt(7, 6)]
    mx.random.seed(42)
    s1 = Server(net, buckets=[(2, 8)], max_new_tokens=5, top_k=10)
    o1 = s1.generate(prompts, temperature=1.0)
    mx.random.seed(42)
    s2 = Server(net, buckets=[(2, 8)], max_new_tokens=5, top_k=10)
    o2 = s2.generate(prompts, temperature=1.0)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
        assert (a >= 0).all() and (a < V).all()
    # mixed greedy/sampled in ONE batch: greedy rows stay greedy
    s3 = Server(net, buckets=[(2, 8)], max_new_tokens=5, top_k=10)
    rg = s3.submit(prompts[0], temperature=0.0)
    s3.submit(prompts[1], temperature=1.0)
    s3.run()
    ref = net.generate(nd.array(prompts[0][None]),
                       max_new_tokens=5).asnumpy()[0]
    np.testing.assert_array_equal(rg.tokens(), ref)


def test_eos_finishes_early(net):
    """A request stops at its eos token and frees the slot."""
    p = _prompt(8, 4)
    probe = Server(net, buckets=[(1, 8)], max_new_tokens=6)
    gen = probe.generate([p])[0][len(p):].astype(int)
    # pick the eos so its FIRST occurrence is the stop point
    eos, stop_at = int(gen[-1]), int(np.nonzero(gen == gen[-1])[0][0])
    srv = Server(net, buckets=[(1, 8)], max_new_tokens=6, eos_id=eos)
    req = srv.submit(p)
    srv.run()
    assert req.state == "done"
    assert len(req.generated) == stop_at + 1
    assert req.generated[-1] == eos
    assert srv.sched.buckets[0].n_active() == 0


# -- the zero-retrace / one-dispatch contract --------------------------------

def test_steady_state_one_dispatch_zero_retraces(net):
    """After the bucket's programs exist, EVERY decode step is exactly
    one engine dispatch and compiles nothing — across admissions,
    evictions, and finishes (admits add one prefill dispatch each,
    never a compile)."""
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=8)
    srv.generate([_prompt(9, 5)])            # warm both programs
    telemetry.clear_events()
    m0, f0 = engine.compile_counts()
    size0 = engine.cache_info()["size"]
    r1 = srv.submit(_prompt(10, 4))
    r2 = srv.submit(_prompt(11, 7))
    st = srv.step()                          # 2 admits + 1 decode
    assert st["admitted"] == 2
    d0 = engine.dispatch_count()
    srv.step()                               # steady decode
    assert engine.dispatch_count() - d0 == 1
    srv.evict(r1, reason="churn")
    srv.submit(_prompt(12, 3))
    srv.run()
    m1, f1 = engine.compile_counts()
    assert (m1 - m0, f1 - f0) == (0, 0)
    assert engine.cache_info()["size"] == size0   # no new executables
    assert telemetry.events("retrace") == []
    stats = srv.stats()["buckets"]["2x8"]
    assert stats["steady_dispatches"] > 0
    assert stats["steady_misses"] == 0
    assert stats["steady_fresh_compiles"] == 0
    assert r2.state == "done"


def test_decode_multi_parity_and_bulking(net):
    """decode_steps=K: token-identical to per-step decode, one
    dispatch (and one host sync) per K tokens."""
    prompts = [_prompt(13, 5), _prompt(14, 8)]
    s1 = Server(net, buckets=[(2, 8)], max_new_tokens=8)
    o1 = s1.generate(prompts, decode_steps=1)
    s2 = Server(net, buckets=[(2, 8)], max_new_tokens=8)
    o2 = s2.generate(prompts, decode_steps=7)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    # bulked steady state: one dispatch per K-token round
    s3 = Server(net, buckets=[(2, 8)], max_new_tokens=15)
    s3.generate([_prompt(15, 4)], decode_steps=7)  # warm all programs
    r = s3.submit(_prompt(16, 4))
    s3.step(decode_steps=7)          # admit + first bulk: the first
    assert len(r.generated) == 1     # token is read, 7 are owed
    d0 = engine.dispatch_count()
    st = s3.step(decode_steps=7)     # steady: ONE dispatch, and the
    assert engine.dispatch_count() - d0 == 1     # first bulk arrives
    assert len(r.generated) == 8 and st["tokens"] == 7
    # the budget was spent at that dispatch: the slot is free by COUNT
    assert r.state == "active" and s3.sched.buckets[0].n_active() == 0
    st = s3.step(decode_steps=7)     # nothing to enqueue: a read only
    assert engine.dispatch_count() - d0 == 1
    assert len(r.generated) == 15 and st["tokens"] == 7
    assert r.state == "done" and s3.idle()


# -- warm start (PR 5 acceptance applied to serving) --------------------------

def test_warm_start_zero_fresh_compiles(net, tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    prompts = [_prompt(17, 5), _prompt(18, 8)]
    engine.clear_cache()
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=5)
    cold = srv.generate(prompts)
    man = str(tmp_path / "serving.json")
    srv.save_signature(man)

    # "fresh process": memory tier emptied, persistent tier kept
    engine.clear_cache()
    engine.reset_counters()
    srv2 = Server(net, buckets=[(2, 8)], max_new_tokens=5)
    assert srv2.warm_start(man)
    assert srv2.warm_started
    warm = srv2.generate(prompts)
    assert engine.cache_info()["fresh_compiles"] == 0
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)
    # warm-started variants count as ALREADY WARM: every live dispatch
    # is steady state, and the warm path stayed compile-free
    st = srv2.stats()["buckets"]["2x8"]
    assert st["steady_dispatches"] > 0
    assert st["steady_misses"] == 0
    assert st["steady_fresh_compiles"] == 0


def test_warm_start_fail_open(net, tmp_path, monkeypatch):
    """Mismatched manifests degrade to cold compile (False + event),
    never a crash."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=5)
    srv.generate([_prompt(19, 4)])
    man = str(tmp_path / "serving.json")
    srv.save_signature(man)
    # different bucket config -> structural mismatch
    other = Server(net, buckets=[(4, 8)], max_new_tokens=5)
    assert other.warm_start(man) is False
    # garbage file
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write("{")
    assert other.warm_start(bad) is False
    evs = telemetry.events("warm_start")
    assert any(e.get("ok") is False for e in evs)
    # still serves (cold) after the failed warm start
    out = other.generate([_prompt(19, 4)])
    assert len(out[0]) == 4 + 5


def test_save_signature_requires_traffic(net):
    srv = Server(net, buckets=[(1, 8)], max_new_tokens=4)
    with pytest.raises(MXNetError, match="serve at least one"):
        srv.save_signature("/tmp/never.json")


# -- failure protocol ---------------------------------------------------------

def test_poison_recover_round_trip(net):
    """A post-donation dispatch failure poisons the pool; recover()
    rebuilds the pages, requeues residents, and the replayed request
    finishes with the exact reference tokens."""
    p = _prompt(20, 5)
    ref = Server(net, buckets=[(2, 8)], max_new_tokens=5).generate([p])[0]
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=5)
    req = srv.submit(p)
    srv.step()
    faults.configure("dispatch_post:nth=1")
    try:
        with pytest.raises(MXNetError, match="recover"):
            srv.step()
    finally:
        faults.clear()
    assert srv.stats()["poisoned"]
    with pytest.raises(MXNetError, match="recover"):
        srv.step()                      # latched until recovery
    assert srv.recover() == 1
    srv.run()
    np.testing.assert_array_equal(req.tokens(), ref)
    evs = telemetry.events("recovery")
    assert any(e.get("where") == "serving" for e in evs)


def test_evict_after_finish_is_noop(net):
    """Evicting a request that already finished must not wipe its
    output, flip its state, or skew the lifecycle counters."""
    telemetry.reset()
    srv = Server(net, buckets=[(1, 4)], max_new_tokens=2)
    r = srv.submit(_prompt(27, 3))
    srv.run()
    assert r.state == "done"
    before = r.tokens().copy()
    assert srv.evict(r, reason="late") is False
    assert r.state == "done"
    np.testing.assert_array_equal(r.tokens(), before)
    snap = telemetry.snapshot()["counters"]
    assert snap.get("mxtpu_serving_requests_evicted_total", 0) == 0
    assert telemetry.events("request_evicted") == []


def test_failed_admit_requeues_pending_placements(net):
    """A pre-dispatch admit failure must not strand the OTHER
    requests admissions() already placed: everyone goes back to the
    queue and a later round serves them all correctly."""
    prompts = [_prompt(28, 4), _prompt(29, 6)]
    refs = Server(net, buckets=[(2, 8)],
                  max_new_tokens=4).generate(prompts)
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=4)
    r1, r2 = [srv.submit(p) for p in prompts]
    faults.configure("dispatch:nth=1")    # first admit dispatch dies
    try:
        with pytest.raises(RuntimeError, match="injected fault"):
            srv.step()
    finally:
        faults.clear()
    # nothing stranded in a half-admitted slot, FIFO order preserved
    assert srv.sched.buckets[0].n_active() == 0
    assert [r.id for r in srv.sched.queue] == [r1.id, r2.id]
    srv.run()
    for r, ref in zip((r1, r2), refs):
        assert r.state == "done"
        np.testing.assert_array_equal(r.tokens(), ref)


def test_pre_dispatch_fault_is_transient(net, monkeypatch):
    """A PRE-donation fault (buffers alive) is absorbed by the
    engine's bounded retry — no poison, the request completes."""
    monkeypatch.setenv("MXTPU_DISPATCH_RETRIES", "2")
    p = _prompt(21, 5)
    ref = Server(net, buckets=[(1, 8)], max_new_tokens=4).generate([p])[0]
    srv = Server(net, buckets=[(1, 8)], max_new_tokens=4)
    req = srv.submit(p)
    srv.step()                          # warm the programs first
    faults.configure("dispatch:nth=1")
    try:
        srv.run()
    finally:
        faults.clear()
    assert not srv.stats()["poisoned"]
    np.testing.assert_array_equal(req.tokens(), ref)


# -- telemetry ----------------------------------------------------------------

def test_serving_telemetry_events_and_metrics(net):
    telemetry.reset()
    srv = Server(net, buckets=[(1, 4)], max_new_tokens=3, max_queue=1)
    r1 = srv.submit(_prompt(22, 3))
    srv.step()                          # r1 admitted, queue empty
    srv.submit(_prompt(23, 2))          # queued (slot busy)
    with pytest.raises(MXNetError, match="queue full"):
        srv.submit(_prompt(24, 2))
    oom = telemetry.events("slot_oom")
    assert oom and oom[-1]["queue_depth"] == 1
    srv.evict(r1, reason="test-evict")
    evs = telemetry.events("request_evicted")
    assert evs and evs[-1]["reason"] == "test-evict"
    srv.run()
    snap = telemetry.snapshot()
    c = snap["counters"]
    assert c["mxtpu_serving_requests_total"] == 2
    assert c["mxtpu_serving_requests_completed_total"] == 1
    assert c["mxtpu_serving_requests_evicted_total"] == 1
    assert c["mxtpu_serving_tokens_total"] >= 3
    hist = telemetry.histogram(
        "mxtpu_serving_ttft_seconds",
        "submit -> first generated token (s)")
    assert hist.summary()["count"] == 2
    assert hist.quantile(0.5) is not None
    assert hist.quantile(0.99) >= hist.quantile(0.5)


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def _recorded_spans(fn):
    """Run ``fn`` with the profiler's sink on; its complete ('X') events."""
    from mxnet_tpu import profiler
    profiler.set_state("run")
    try:
        fn()
    finally:
        profiler.set_state("stop")
    with profiler._lock:
        events = [e for e in profiler._events if e["ph"] == "X"]
        profiler._events.clear()
    return events


@pytest.mark.time_limit(120)
def test_round_spans_nest_and_share_the_request_id(net):
    """One ``step()`` that admits one request and decodes another:
    round > admit / decode > dispatch > engine.execute for what it
    ENQUEUES, then round > decode / admit > token_read for what it
    READS (the previous round's decode, this round's first token);
    every span of the admission carries its one ``req`` id, and with
    the profiler stopped (and no jax session) a round appends nothing."""
    from mxnet_tpu import profiler
    srv = Server(net, buckets=[(2, 8)], max_new_tokens=6)
    srv.submit(_prompt(30, 4))
    srv.step()                          # compiles prefill + decode
    req = srv.submit(_prompt(31, 5))
    events = _recorded_spans(srv.step)

    def named(name, within=None):
        return sorted((e for e in events if e["name"] == name
                       and (within is None or _inside(e, within))),
                      key=lambda e: e["ts"])

    rnd, = named("mxtpu.serving.round")
    assert rnd["args"] == {"round": 2} and rnd["cat"] == "serving"
    assert all(_inside(e, rnd) and e["tid"] == rnd["tid"]
               for e in events if e is not rnd)
    assert len(named("mxtpu.serving.expire", rnd)) == 1
    assert len(named("mxtpu.serving.schedule", rnd)) == 1
    admit, first = named("mxtpu.serving.admit", rnd)
    decode, late = named("mxtpu.serving.decode", rnd)
    assert admit["args"] == {"req": req.id, "bucket": 8, "rung": 8,
                             "slot": 1}
    assert decode["args"] == {"bucket": 8, "active": 2}
    assert late["args"] == {"bucket": 8}
    assert first["args"] == {"bucket": 8, "req": req.id, "rung": 8}
    # everything is enqueued before anything is read
    order = [admit, decode, late, first]
    assert all(a["ts"] + a["dur"] <= b["ts"]
               for a, b in zip(order, order[1:]))
    for parent, kind in ((admit, "prefill"), (decode, "decode")):
        for leaf in ("build_inputs", "bookkeeping"):
            assert len(named("mxtpu.serving." + leaf, parent)) == 1
        assert named("mxtpu.serving.token_read", parent) == []
        dispatch, = named("mxtpu.serving.dispatch", parent)
        assert dispatch["args"]["kind"] == kind
        assert len(named("mxtpu.serving.flatten", dispatch)) == 1
        assert len(named("mxtpu.serving.state_adopt", dispatch)) == 1
        lookup, = named("mxtpu.engine.lookup", dispatch)
        assert len(named("mxtpu.engine.telemetry", dispatch)) == 1
        execute, = named("mxtpu.engine.execute", dispatch)
        assert lookup["args"]["op"] == execute["args"]["op"]
        assert execute["args"]["op"].endswith(kind)
    for parent in (late, first):
        for leaf in ("token_read", "bookkeeping"):
            assert len(named("mxtpu.serving." + leaf, parent)) == 1
        assert named("mxtpu.serving.dispatch", parent) == []
    for parent, leaves in ((admit, ("build_inputs", "dispatch",
                                    "bookkeeping")),
                           (first, ("token_read", "bookkeeping"))):
        for leaf in leaves:
            assert named("mxtpu.serving." + leaf,
                         parent)[0]["args"]["req"] == req.id
    assert {e["args"]["req"] for e in events
            if "req" in e.get("args", {})} == {req.id}
    assert not any("req" in e.get("args", {}) for e in events
                   if _inside(e, decode) or _inside(e, late))

    srv.step()                          # both sinks off
    assert profiler._events == []


@pytest.mark.time_limit(120)
def test_admit_stamp_and_queue_wait_histogram(net):
    """``Request.admit_t`` is set where the admission starts, and
    ``mxtpu_serving_queue_wait_seconds`` takes one observation per
    admission: a request that waited for a slot shows its wait."""
    telemetry.reset()
    srv = Server(net, buckets=[(1, 8)], max_new_tokens=3)
    first = srv.submit(_prompt(32, 4))
    second = srv.submit(_prompt(33, 4))     # one slot: it must queue
    assert first.admit_t is None and second.admit_t is None
    srv.step()
    assert second.admit_t is None
    assert first.submit_t <= first.admit_t <= first.first_token_t
    srv.run()
    # the slot is free once the first request's LAST token is enqueued:
    # the second is admitted before that token is read
    assert first.done_t > second.admit_t > first.first_token_t
    assert second.first_token_t > first.done_t
    hist = telemetry.histogram(
        "mxtpu_serving_queue_wait_seconds",
        "submit -> start of the admission (s)").summary()
    assert hist["count"] == 2
    assert hist["sum"] == pytest.approx(
        (first.admit_t - first.submit_t)
        + (second.admit_t - second.submit_t))


def test_evict_event_survives_dispatch_flood(net):
    """request_evicted/slot_oom live in the RETAINED rare ring: a
    flood of dispatch events cannot evict the forensics."""
    telemetry.reset()
    srv = Server(net, buckets=[(1, 4)], max_new_tokens=6)
    r = srv.submit(_prompt(25, 3))
    srv.step()
    assert srv.evict(r, reason="forensic") is True
    for _ in range(2000):
        telemetry.record_event("dispatch", op="flood")
    evs = telemetry.events("request_evicted")
    assert any(e.get("reason") == "forensic" for e in evs)


def test_env_default_buckets(net, monkeypatch):
    monkeypatch.setenv("MXTPU_SERVING_SLOTS", "3")
    monkeypatch.setenv("MXTPU_SERVING_BUCKETS", "16")
    monkeypatch.setenv("MXTPU_SERVING_MAX_NEW_TOKENS", "7")
    monkeypatch.setenv("MXTPU_SERVING_MAX_QUEUE", "9")
    srv = Server(net)
    assert [(b.slots, b.prompt_len) for b in srv.sched.buckets] \
        == [(3, 16)]
    assert srv.max_new_tokens == 7
    assert srv.sched.max_queue == 9


# -- mxlint MXL601 ------------------------------------------------------------

_BAD_LOOP = """
def handle(requests, net):
    for toks in requests:
        caches = net.init_cache(1, 64)
        logits = net.prefill(toks, caches)
        out = net.generate(toks, 32)
    return out
"""


def test_mxl601_static_corpus():
    from mxnet_tpu import analysis
    found = analysis.analyze_source(_BAD_LOOP, "svc.py")
    assert [f.rule for f in found] == ["MXL601"]
    assert "docs/serving.md" in found[0].message


def test_mxl601_markers_and_suppression():
    from mxnet_tpu import analysis
    quiet = _BAD_LOOP + "\nfrom mxnet_tpu.serving import Server\n"
    assert not analysis.analyze_source(quiet, "svc.py")
    sup = _BAD_LOOP.replace(
        "logits = net.prefill(toks, caches)",
        "logits = net.prefill(toks, caches)  # mxlint: disable=MXL601")
    assert not [f for f in analysis.analyze_source(sup, "svc.py")
                if f.rule == "MXL601"]
    # a model's own decode loop (self receiver / layer induction) is
    # the implementation, not a request loop
    own = """
class M:
    def generate(self, toks, n):
        for i in range(n):
            logits = self.decode_step(toks, self.caches, i)
        for layer in self.layers:
            layer.prefill(toks, self.caches)
        return logits
"""
    assert not analysis.analyze_source(own, "own.py")


def test_mxserve_cli_smoke(capsys):
    """tools/mxserve.py smoke drains its burst with the zero-retrace
    contract held (exit 0) and renders the per-bucket table."""
    import importlib
    mxserve = importlib.import_module("tools.mxserve")
    assert mxserve.main(["smoke"]) == 0
    out = capsys.readouterr().out
    assert "zero-retrace contract held" in out
    assert "4x8" in out


def test_mxl601_runtime_twin(net):
    """analyze_serving is quiet on a healthy server and fires when a
    bucket recorded steady-state compiles."""
    from mxnet_tpu import analysis
    srv = Server(net, buckets=[(1, 4)], max_new_tokens=2)
    srv.generate([_prompt(26, 3)])
    assert analysis.analyze_serving() == []
    fs, ok = analysis.self_check()
    assert ok and not [f for f in fs if f.rule == "MXL601"]
    # a steady-state compile is the hazard
    key = srv.sched.buckets[0].key
    srv._bucket_stats[key]["steady_dispatches"] = 5
    srv._bucket_stats[key]["steady_misses"] = 3
    found = analysis.analyze_serving()
    assert [f.rule for f in found] == ["MXL601"]
    assert "1x4" in found[0].message
    fs2, _ = analysis.self_check()
    assert [f for f in fs2 if f.rule == "MXL601"]


# -- a mixed state spec: SambaY behind the same Server ------------------------
# (docs/serving.md, "State kinds": recurrent, conv, window and full-KV
# buffers in one pool; models/sambay.py)

@pytest.fixture(scope="module")
def hybrid():
    from mxnet_tpu.models import SambaYForCausalLM, sambay_tiny
    mx.random.seed(1)
    np.random.seed(1)
    lm = SambaYForCausalLM(sambay_tiny(vocab_size=V))
    lm.initialize(mx.init.Xavier())
    return lm


def _reference_tokens(lm, prompt, new):
    return lm.generate(nd.array(prompt[None]),
                       max_new_tokens=new).asnumpy()[0]


def test_hybrid_pool_follows_the_models_spec(hybrid):
    pool = KVCachePool(hybrid, slots=3, cache_len=20, dtype="bfloat16")
    assert pool.num_buffers - 1 == len(hybrid.state_spec(3, 20)) == 12
    kinds = {k for _n, k, _s, _d in pool.spec}
    assert kinds == {"conv", "ssm", "kv_window", "kv_full"}
    for (_n, kind, shape, dtype), buf in zip(pool.spec, pool.flat()):
        assert tuple(buf.shape) == shape and str(buf.dtype) == dtype
        # a recurrent state stays float32 whatever the cache dtype
        assert dtype == ("float32" if kind == "ssm" else "bfloat16")
    by = pool.bytes_by_kind()
    assert sum(by.values()) == pool.nbytes()
    assert by["ssm"] == 3 * 3 * 4 * 128 * 4
    with pytest.raises(MXNetError, match="adopt"):
        pool.adopt(pool.flat()[:3])


def test_pool_rejects_a_spec_outside_the_contract(hybrid):
    class Bad:
        def __init__(self, rows):
            self.rows = rows

        def state_spec(self, slots, cache_len, dtype):
            return self.rows
    with pytest.raises(MXNetError, match="kind"):
        KVCachePool(Bad([("x", "pages", (2, 4), "float32")]), 2, 4)
    with pytest.raises(MXNetError, match="slot dim"):
        KVCachePool(Bad([("x", "ssm", (3, 4), "float32")]), 2, 4)
    with pytest.raises(MXNetError, match="no buffer"):
        KVCachePool(Bad([]), 2, 4)


def test_hybrid_greedy_parity_and_one_dispatch(hybrid):
    """Continuous batching over SSM, conv, window and full-KV state is
    bit-transparent for greedy, prompts shorter and longer than the
    window (8) side by side; steady state is one dispatch a round."""
    prompts = [_prompt(40, 5), _prompt(41, 14), _prompt(42, 2),
               _prompt(43, 16)]
    srv = Server(hybrid, buckets=[(2, 16)], max_new_tokens=12)
    outs = srv.generate(prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _reference_tokens(hybrid, p, 12))
    st = srv.stats()["buckets"]["2x16"]
    assert st["steady_misses"] == 0 and st["steady_fresh_compiles"] == 0
    r = srv.submit(_prompt(44, 9))
    srv.step()
    d0 = engine.dispatch_count()
    srv.step()
    assert engine.dispatch_count() - d0 == 1
    srv.run()
    np.testing.assert_array_equal(
        r.tokens(), _reference_tokens(hybrid, _prompt(44, 9), 12))
    # K-step bulking carries every kind of state through lax.scan
    bulk = srv.generate(prompts[:2], decode_steps=4)
    for p, out in zip(prompts, bulk):
        np.testing.assert_array_equal(out, _reference_tokens(hybrid, p, 12))


def test_hybrid_readmitted_slot_keeps_no_recurrent_trace(hybrid):
    """Admit, evict, re-admit: the request that takes over a slot gets
    EXACTLY the tokens it gets in a fresh server.  A recurrent state has
    no validity mask to hide behind: admission must replace all of it."""
    pa, pb = _prompt(45, 11), _prompt(46, 13)
    want = _reference_tokens(hybrid, pa, 10)
    srv = Server(hybrid, buckets=[(1, 16)], max_new_tokens=10)
    rb = srv.submit(pb)
    for _ in range(6):
        srv.step()                       # slot 0 now deep in B's state
    ssm = [i for i, r in enumerate(srv._pools[(1, 16)].spec)
           if r[1] == "ssm"]
    before = [np.asarray(srv._pools[(1, 16)].flat()[i]) for i in ssm]
    assert all(np.abs(b).max() > 0 for b in before)
    srv.evict(rb, reason="preempt")
    ra = srv.submit(pa)
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), want)
    # and next to an evicted neighbour's live garbage (two slots)
    srv2 = Server(hybrid, buckets=[(2, 16)], max_new_tokens=10)
    ra2, rb2 = srv2.submit(pa), srv2.submit(pb)
    srv2.step()
    srv2.evict(rb2, reason="preempt")
    srv2.run()
    np.testing.assert_array_equal(ra2.tokens(), want)


def test_hybrid_resize_slots_migrates_every_kind(hybrid):
    pa, pb = _prompt(47, 6), _prompt(48, 12)
    want = [_reference_tokens(hybrid, p, 10) for p in (pa, pb)]
    srv = Server(hybrid, buckets=[(2, 16)], max_new_tokens=10)
    ra, rb = srv.submit(pa), srv.submit(pb)
    srv.step()
    srv.step()
    rec = srv.resize_slots(3)
    assert rec["migrated"] == 2 and rec["requeued"] == 0
    assert rec["prewarmed_variants"] == 2
    assert [tuple(b.shape)[0] for b in srv._pools[(3, 16)].flat()] \
        == [3] * 13
    m0, f0 = engine.compile_counts()
    srv.run()
    assert engine.compile_counts() == (m0, f0)     # the pre-warm held
    np.testing.assert_array_equal(ra.tokens(), want[0])
    np.testing.assert_array_equal(rb.tokens(), want[1])
    snap = telemetry.snapshot()["gauges"]
    assert snap["mxtpu_serving_state_bytes_b3x16_ssm"] == 3 * 3 * 4 * 128 * 4


def test_hybrid_poison_recover_round_trip(hybrid):
    p = _prompt(49, 9)
    want = _reference_tokens(hybrid, p, 8)
    srv = Server(hybrid, buckets=[(2, 16)], max_new_tokens=8)
    req = srv.submit(p)
    srv.step()
    faults.configure("dispatch_post:nth=1")
    try:
        with pytest.raises(MXNetError, match="recover"):
            srv.step()
    finally:
        faults.clear()
    assert srv.recover() == 1
    assert all(float(np.abs(np.asarray(b, np.float32)).max()) == 0.0
               for b in srv._pools[(2, 16)].flat())
    srv.run()
    np.testing.assert_array_equal(req.tokens(), want)


def test_hybrid_warm_start_carries_the_spec(hybrid, tmp_path, monkeypatch):
    import json
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    prompts = [_prompt(50, 5), _prompt(51, 12)]
    engine.clear_cache()
    srv = Server(hybrid, buckets=[(2, 16)], max_new_tokens=6,
                 cache_dtype="bfloat16")
    cold = srv.generate(prompts)
    man = str(tmp_path / "hybrid.json")
    srv.save_signature(man)
    rows = json.load(open(man))["buckets"][0]["state"]
    assert ["layer0_ssm", "ssm", [2, 4, 128], "float32"] in rows
    assert ["layer5_k", "kv_full", [2, 22, 4, 8], "bfloat16"] in rows
    engine.clear_cache()
    engine.reset_counters()
    srv2 = Server(hybrid, buckets=[(2, 16)], max_new_tokens=6,
                  cache_dtype="bfloat16")
    assert srv2.warm_start(man)
    warm = srv2.generate(prompts)
    assert engine.cache_info()["fresh_compiles"] == 0
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)
    # another cache dtype is another spec: fail open, by the hash
    other = Server(hybrid, buckets=[(2, 16)], max_new_tokens=6)
    assert other.warm_start(man) is False
    # and a manifest whose spec rows were edited names the buffer
    m = json.load(open(man))
    m["buckets"][0]["state"][1][3] = "bfloat16"
    with open(man, "w") as f:
        json.dump(m, f)
    srv3 = Server(hybrid, buckets=[(2, 16)], max_new_tokens=6,
                  cache_dtype="bfloat16")
    assert srv3.warm_start(man) is False
    assert any("state spec mismatch" in str(e.get("reason"))
               and "layer0_ssm" in str(e.get("reason"))
               for e in telemetry.events("warm_start"))


def test_llama_declares_the_spec_it_always_had(net):
    """Llama/Mistral run the same state plane: two K/V buffers a layer,
    in the flat order the programs always took."""
    spec = net.state_spec(2, 8, "bfloat16")
    assert [n for n, _k, _s, _d in spec] == [
        "layer0_k", "layer0_v", "layer1_k", "layer1_v"]
    assert {(k, s, d) for _n, k, s, d in spec} == {
        ("kv_full", (2, 8, 2, 16), "bfloat16")}
    # init_cache IS the spec, zeroed: one form of the state, flat
    caches = net.init_cache(2, 8, dtype="bfloat16")
    assert [(c.shape, str(c.dtype)) for c in caches] == [
        (s, d) for _n, _k, s, d in spec]


def test_rolling_cache_is_the_spec_at_the_window(hybrid):
    """The rolling buffer is the same flat list, only shorter; the hybrid
    decoder's window pages are per layer."""
    from mxnet_tpu.models import LlamaForCausalLM, get_llama
    mx.random.seed(5)
    win = LlamaForCausalLM(get_llama("llama_tiny", vocab_size=61,
                                     sliding_window=4))
    win.initialize()
    assert [c.shape[1] for c in win.init_cache(1, 8, rolling=True)] \
        == [4] * 4
    assert {k for _n, k, _s, _d in win.state_spec(1, 8)} == {"kv_window"}
    lens = {n: s[1] for n, k, s, _d in hybrid.state_spec(1, 16)
            if k.startswith("kv_")}
    assert set(lens.values()) == {8, 16}        # windows of 8, ONE full page


# -- the resident RNG key (docs/serving.md, "Sampling") -----------------------
# ONE base key a server, drawn from the global stream and kept on the
# device; every dispatch hands it over beside a counter that the program
# folds in.  Each contract below holds for both state planes.

@pytest.fixture(params=["llama", "hybrid"])
def lm_bucket(request):
    """A decoder and a two-slot bucket that fits its prompts."""
    if request.param == "llama":
        return request.getfixturevalue("net"), (2, 8)
    return request.getfixturevalue("hybrid"), (2, 16)


def _host_keys():
    return telemetry.counter("mxtpu_serving_host_keys_total").value


def test_one_host_key_a_server(lm_bucket, monkeypatch):
    """The host draws a key at a server's first dispatch and never
    again: no steady round, admitting or decoding, touches the stream."""
    from mxnet_tpu import random as rnd
    lm, bucket = lm_bucket
    k0 = _host_keys()
    srv = Server(lm, buckets=[bucket], max_new_tokens=6)
    assert _host_keys() == k0               # drawn when first needed
    srv.submit(_prompt(60, 4), temperature=1.0)
    srv.step()                              # first prefill AND decode
    assert _host_keys() == k0 + 1
    calls = []
    real = rnd._next_key_nd
    monkeypatch.setattr(rnd, "_next_key_nd",
                        lambda ctx: calls.append(ctx) or real(ctx))
    d0 = engine.dispatch_count()
    for i in range(5):                      # admissions between decodes
        srv.submit(_prompt(61 + i, 3 + i), temperature=float(i % 2))
        srv.step()
    srv.run()
    assert engine.dispatch_count() - d0 >= 10
    assert calls == [] and _host_keys() == k0 + 1
    # a second server is a second key, not a shared one
    Server(lm, buckets=[bucket], max_new_tokens=6).generate(
        [_prompt(60, 4)])
    assert len(calls) == 1 and _host_keys() == k0 + 2


def test_keys_differ_by_dispatch_and_by_row(lm_bucket):
    """Every dispatch folds its own count into the base key and every
    row its own index: the same prompt draws other tokens in the next
    round, and in the neighbouring slot of the same round."""
    lm, (slots, plen) = lm_bucket
    p = _prompt(62, 5)
    mx.random.seed(11)
    one = Server(lm, buckets=[(1, plen)], max_new_tokens=12)
    first = one.generate([p], temperature=1.0)[0]
    again = one.generate([p], temperature=1.0)[0]   # same slot, later
    assert not np.array_equal(first, again)
    two = Server(lm, buckets=[(slots, plen)], max_new_tokens=12)
    a, b = two.generate([p, p], temperature=1.0)    # one round, two rows
    assert not np.array_equal(a, b)
    for out in (first, again, a, b):
        assert len(out) == 5 + 12
        assert (out >= 0).all() and (out < V).all()


def test_reseed_takes_effect_at_the_next_dispatch(lm_bucket):
    """``mx.random.seed`` between requests re-draws the base key at the
    next dispatch (one more host key) and restarts the count: the same
    seed gives the same tokens, another seed gives others."""
    lm, bucket = lm_bucket
    p = [_prompt(63, 4), _prompt(64, 6)]
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    k0 = _host_keys()
    mx.random.seed(5)
    a = srv.generate(p, temperature=1.0)
    mx.random.seed(6)
    b = srv.generate(p, temperature=1.0)
    mx.random.seed(5)
    c = srv.generate(p, temperature=1.0)
    assert _host_keys() == k0 + 3
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, z)
    assert any(not np.array_equal(x, y) for x, y in zip(a, b))
    # unseeded, the server keeps its key: no draw, and no repeat
    d = srv.generate(p, temperature=1.0)
    assert _host_keys() == k0 + 3
    assert any(not np.array_equal(x, y) for x, y in zip(c, d))


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_mixed_batch_greedy_exact_sampled_seeded(lm_bucket, decode_steps):
    """One batch, one greedy and one sampled row, per step and K steps a
    dispatch: the greedy row is bit-equal to ``generate`` (the sampler's
    key never reaches it), the sampled row is a function of the seed."""
    lm, bucket = lm_bucket
    pg, ps = _prompt(65, 5), _prompt(66, 7)

    def serve():
        mx.random.seed(21)
        srv = Server(lm, buckets=[bucket], max_new_tokens=9, top_k=10)
        rg = srv.submit(pg, temperature=0.0)
        rs = srv.submit(ps, temperature=1.0)
        srv.run(decode_steps=decode_steps)
        return rg.tokens(), rs.tokens()

    g1, s1 = serve()
    g2, s2 = serve()
    np.testing.assert_array_equal(g1, _reference_tokens(lm, pg, 9))
    np.testing.assert_array_equal(g1, g2)
    np.testing.assert_array_equal(s1, s2)
    assert len(s1) == 7 + 9 and (s1 >= 0).all() and (s1 < V).all()
    # the scanned steps fold their index in: not one draw K times
    assert len(set(s1[7:].tolist())) > 1


def test_warm_start_key_input_round_trip_and_old_manifest(
        lm_bucket, tmp_path):
    """A manifest of today's call shape (the pool: state and last
    tokens, all donated; the kind's own inputs; the base key; the
    counter) warm-starts with 0 fresh compiles.  One from before the key
    was resident (a key made per dispatch as the LAST input) and one
    from before the last tokens lived in the pool (a host-made ``tok``
    input, the state alone donated) fail open: False, a ``warm_start``
    event that names the key input and the new pool buffer, nothing
    pre-compiled, and the next step cold and correct."""
    import json
    lm, bucket = lm_bucket
    prompts = [_prompt(67, 5), _prompt(68, 7)]
    engine.clear_cache()
    srv = Server(lm, buckets=[bucket], max_new_tokens=5)
    cold = srv.generate(prompts)
    man = str(tmp_path / "serving.json")
    srv.save_signature(man)
    m = json.load(open(man))
    n_par, n_pool = len(srv._param_nds), srv._pools[bucket].num_buffers
    assert {v["kind"] for v in m["variants"]} == {"prefill", "decode"}
    for v in m["variants"]:
        # prefill: prompt, last_pos, slot, temp; decode: off, active, temp
        own = {"prefill": 4, "decode": 3}[v["kind"]]
        assert len(v["avals"]) == n_par + n_pool + own + 2
        assert v["donate"] == list(range(n_par, n_par + n_pool))
        assert v["avals"][n_par + n_pool - 1] == \
            [[bucket[0], 1], "float32"]                 # the last tokens
        assert v["avals"][-1] == [[], "uint32"]         # the counter
        assert v["avals"][-2][1] == "uint32"            # the base key
        assert v["avals"][-3][1] == "float32"           # temp

    engine.clear_cache()
    engine.reset_counters()
    srv2 = Server(lm, buckets=[bucket], max_new_tokens=5)
    assert srv2.warm_start(man) is True
    fresh = engine.cache_info()["fresh_compiles"]
    warm = srv2.generate(prompts)
    assert engine.cache_info()["fresh_compiles"] == fresh
    st = srv2.stats()["buckets"]["%dx%d" % bucket]
    assert st["steady_misses"] == 0 and st["steady_fresh_compiles"] == 0

    # before PR 32: the state alone was donated (decode then took a
    # host-made ``tok`` of the token vector's very aval, so only the
    # donation tells them apart); before PR 30: no counter either
    before_32 = json.loads(json.dumps(m))
    for v in before_32["variants"]:
        v["donate"] = v["donate"][:-1]
        if v["kind"] == "prefill":
            del v["avals"][n_par + n_pool - 1]
    before_30 = json.loads(json.dumps(before_32))
    for v in before_30["variants"]:
        del v["avals"][-1]
    for name, manifest in (("b32", before_32), ("b30", before_30)):
        old = str(tmp_path / f"serving_{name}.json")
        with open(old, "w") as f:
            json.dump(manifest, f)
        engine.clear_cache()
        engine.reset_counters()
        telemetry.clear_events()
        srv3 = Server(lm, buckets=[bucket], max_new_tokens=5)
        fresh = engine.cache_info()["fresh_compiles"]   # the pool's zeros
        assert srv3.warm_start(old) is False
        ev = telemetry.events("warm_start")[-1]
        assert ev["ok"] is False and "RNG key input" in ev["reason"]
        assert "`last_token`" in ev["reason"]
        assert not srv3.warm_started and not srv3._warmed
        assert engine.cache_info()["fresh_compiles"] == fresh
    again = srv3.generate(prompts)
    assert engine.cache_info()["fresh_compiles"] >= fresh + 2
    for a, b, c in zip(cold, warm, again):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


# -- one decode ahead of the reads (docs/serving.md, "A round") ---------------
# A slot's last token lives in the pool; a round enqueues its prefills and
# its decodes, then reads the PREVIOUS round's decode tokens and its own
# first tokens.  Each contract below holds for both state planes.

def _counter(name):
    return telemetry.counter(name).value


def _ahead():
    return _counter("mxtpu_serving_decodes_ahead_total")


def _overrun():
    return _counter("mxtpu_serving_overrun_tokens_total")


def _tightest_parent(e, events):
    around = [p for p in events if p is not e and p["tid"] == e["tid"]
              and _inside(e, p)]
    return min(around, key=lambda p: p["dur"]) if around else None


@pytest.mark.time_limit(120)
def test_next_decode_is_enqueued_before_the_last_is_read(lm_bucket):
    """(a) and (f): round n+1's ``engine.execute`` begins before round
    n's tokens are read; every decode but the first of a busy spell is
    enqueued AHEAD; every ``token_read`` is a child of ``decode`` or
    ``admit``, and every round that decodes has a ``decode`` child: the
    nesting ``chipbench/harness/program_spans.py`` reads."""
    lm, bucket = lm_bucket
    srv = Server(lm, buckets=[bucket], max_new_tokens=6)
    srv.generate([_prompt(70, 4)])                  # compile
    a0 = _ahead()

    def two_spells():
        srv.generate([_prompt(71, 5), _prompt(72, 3)])
        srv.generate([_prompt(73, 4)])

    events = _recorded_spans(two_spells)

    def named(name, within=None):
        return sorted((e for e in events if e["name"] == name
                       and (within is None or _inside(e, within))),
                      key=lambda e: e["ts"])

    rounds = named("mxtpu.serving.round")
    decodes = [e for e in named("mxtpu.serving.dispatch")
               if e["args"]["kind"] == "decode"]
    # prefill 1 + 5 decodes a request, the requests of a spell in step
    assert len(decodes) == 10
    assert _ahead() - a0 == len(decodes) - 2        # two busy spells
    reads = named("mxtpu.serving.token_read")
    assert len(reads) == len(decodes) + 3           # + 3 first tokens
    for r in reads:
        assert _tightest_parent(r, events)["name"] in (
            "mxtpu.serving.decode", "mxtpu.serving.admit")
    enqueued_in = {}
    for d in decodes:
        rnd, = [r for r in rounds if _inside(d, r)]
        enqueued_in[rnd["args"]["round"]] = d
        assert named("mxtpu.serving.decode", rnd)
    # a decode's tokens are read in the round AFTER the one that
    # enqueued it, behind that round's own decode where it has one
    late = [r for r in reads if _tightest_parent(r, events)["name"]
            == "mxtpu.serving.decode"]
    assert len(late) == len(decodes)
    for d, r in zip(decodes, late):
        rnd, = [x for x in rounds if _inside(r, x)]
        n = rnd["args"]["round"]
        assert _inside(d, [x for x in rounds
                           if x["args"]["round"] == n - 1][0])
        if n in enqueued_in:
            execute, = named("mxtpu.engine.execute", enqueued_in[n])
            assert execute["ts"] + execute["dur"] <= r["ts"]


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_greedy_parity_with_midstream_admissions(lm_bucket, decode_steps):
    """(b): requests of different budgets leave their slots at different
    rounds and newcomers take the freed slots mid-stream; every one is
    bit-equal to ``generate``, per step and K steps a dispatch."""
    lm, bucket = lm_bucket
    srv = Server(lm, buckets=[bucket], max_new_tokens=11)
    plan = [(_prompt(74, 5), 11), (_prompt(75, 3), 4), (_prompt(76, 6), 7),
            (_prompt(77, 2), 1), (_prompt(78, 4), 9), (_prompt(79, 7), 2)]
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in plan[:3]]
    srv.step(decode_steps=decode_steps)
    srv.step(decode_steps=decode_steps)
    reqs += [srv.submit(p, max_new_tokens=n) for p, n in plan[3:]]
    srv.run(decode_steps=decode_steps)
    for r, (p, n) in zip(reqs, plan):
        assert r.state == "done" and len(r.generated) == n
        np.testing.assert_array_equal(r.tokens(),
                                      _reference_tokens(lm, p, n))
    assert srv.idle()


def test_eos_overrun_is_dropped_and_leaves_no_trace(lm_bucket):
    """(c): an ``eos_id`` is a token's VALUE: the host learns it one
    dispatch late.  The request delivers nothing past it, the one token
    decoded meanwhile is counted and dropped, and the request admitted
    into the slot next gets exactly a fresh server's tokens."""
    lm, (_slots, plen) = lm_bucket
    pa, pb = _prompt(80, 5), _prompt(81, 4)
    gen = _reference_tokens(lm, pa, 8)[len(pa):].astype(int)
    eos = int(gen[1])
    stop_at = int(np.nonzero(gen == eos)[0][0])     # 0 or 1: mid-budget
    srv = Server(lm, buckets=[(1, plen)], max_new_tokens=8, eos_id=eos)
    o0 = _overrun()
    ra, rb = srv.submit(pa), srv.submit(pb, eos_id=-1)
    srv.run()
    assert ra.state == "done" and ra.generated == list(gen[:stop_at + 1])
    assert _overrun() - o0 == 1
    np.testing.assert_array_equal(rb.tokens(),
                                  _reference_tokens(lm, pb, 8))


def test_dispatch_count_invariant_over_a_run_with_a_drain(lm_bucket):
    """(d): the benchmark's invariant (``chipbench/drivers/
    serve_loop.py``): a bucket is decoded in a round if and only if it
    holds an active slot when the round decodes, so dispatches ==
    admissions + busy buckets, round by round, down to the last round,
    which enqueues nothing; every request gets the tokens it asked for."""
    lm, (slots, plen) = lm_bucket
    srv = Server(lm, buckets=[(slots, plen // 2), (slots, plen)],
                 max_new_tokens=7)
    rng = np.random.RandomState(82)
    asked = [(_prompt(83 + i, int(rng.randint(1, plen + 1))),
              int(rng.randint(1, 8))) for i in range(9)]
    srv.generate([_prompt(82, plen // 2), _prompt(82, plen)])  # compile
    decoded, skipped = [], []
    real_decode, real_collect = srv._decode, srv._collect

    def decode(bucket, k):
        assert bucket.n_active() > 0
        decoded.append(bucket)
        return real_decode(bucket, k)

    def collect(n=None):
        # a bucket this round did not decode has the mask it had then
        skipped.extend(b for b in srv.sched.buckets
                       if b not in decoded and b.n_active() > 0)
        return real_collect(n)

    srv._decode, srv._collect = decode, collect
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in asked[:5]]
    rounds = 0
    while not srv.idle():
        if rounds == 3:
            reqs += [srv.submit(p, max_new_tokens=n) for p, n in asked[5:]]
        busy_before = sum(1 for b in srv.sched.buckets if b.n_active())
        del decoded[:]
        d0 = engine.dispatch_count()
        st = srv.step()
        d = engine.dispatch_count() - d0
        assert d == st["admitted"] + len(decoded) and not skipped
        if not st["admitted"]:
            assert len(decoded) == busy_before
        rounds += 1
        assert rounds < 80
    assert d == 0 and st["tokens"] > 0              # the drain's read
    for r, (p, n) in zip(reqs, asked):
        assert r.state == "done" and len(r.generated) == n
        np.testing.assert_array_equal(r.tokens(),
                                      _reference_tokens(lm, p, n))


def test_evict_and_requeue_with_a_decode_outstanding(lm_bucket):
    """(e): what the device still owes an evicted request is dropped,
    not delivered, and a requeued one restarts clean: no stale token of
    its first life reaches its second."""
    lm, bucket = lm_bucket
    pa, pb, pc = _prompt(90, 5), _prompt(91, 4), _prompt(92, 6)
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    ra, rb = srv.submit(pa), srv.submit(pb)
    srv.step()
    srv.step()
    assert srv._owed and rb.owed == 1 and len(rb.generated) == 2
    o0 = _overrun()
    assert srv.evict(rb, reason="user")
    assert rb.state == "evicted" and len(rb.generated) == 2
    assert _overrun() - o0 == 1
    rc = srv.submit(pc)                             # takes rb's slot
    srv.step()
    assert len(rb.generated) == 2 and rb.owed == 0
    o1 = _overrun()
    srv.evict(ra, reason="preempt", requeue=True)   # decode outstanding
    assert ra.state == "queued" and ra.generated == [] and ra.owed == 0
    assert _overrun() - o1 == 1
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), _reference_tokens(lm, pa, 8))
    np.testing.assert_array_equal(rc.tokens(), _reference_tokens(lm, pc, 8))


def test_deadline_expiry_with_a_decode_outstanding(lm_bucket):
    import time
    lm, bucket = lm_bucket
    pa, pb = _prompt(93, 5), _prompt(94, 4)
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    srv.generate([pa])                              # compile
    ra = srv.submit(pa)
    rb = srv.submit(pb, ttl_ms=60_000.0)
    srv.step()
    srv.step()
    rb.deadline = time.perf_counter() - 1.0         # it expires NOW
    had = len(rb.generated)
    o0 = _overrun()
    srv.step()                                      # the sweep runs first
    assert rb.state == "evicted" and rb.evict_reason == "deadline"
    assert len(rb.generated) == had and _overrun() - o0 == 1
    ev = telemetry.events("deadline_evicted")[-1]
    assert ev["request"] == rb.id and ev["generated"] == had
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), _reference_tokens(lm, pa, 8))


def test_resize_and_save_signature_drain_the_owed_reads(lm_bucket, tmp_path):
    """(e): both look at the server from outside a round, so both first
    read what the device owes (``settle``)."""
    lm, (slots, plen) = lm_bucket
    pa, pb = _prompt(95, 5), _prompt(96, 3)
    srv = Server(lm, buckets=[(slots, plen)], max_new_tokens=8)
    ra, rb = srv.submit(pa), srv.submit(pb)
    srv.step()
    srv.step()
    assert srv._owed and len(ra.generated) == 2
    srv.save_signature(str(tmp_path / "sig.json"))
    assert not srv._owed and len(ra.generated) == 3 and ra.owed == 0
    srv.step()
    assert srv._owed
    rec = srv.resize_slots(slots + 1)
    assert rec["migrated"] == 2 and not srv._owed
    assert len(ra.generated) == 4 and len(rb.generated) == 4
    # the slots' last tokens moved with the state
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), _reference_tokens(lm, pa, 8))
    np.testing.assert_array_equal(rb.tokens(), _reference_tokens(lm, pb, 8))
    assert srv.settle() == 0 and srv.idle()


def test_poison_recover_with_a_decode_outstanding(lm_bucket):
    """(e): a dispatch that dies with the pool donated loses what the
    device owed too: ``recover`` requeues the residents AND the request
    that had left its slot by count and was waiting for its last token."""
    lm, bucket = lm_bucket
    pa, pb = _prompt(97, 5), _prompt(98, 4)
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    ra = srv.submit(pa)
    rb = srv.submit(pb, max_new_tokens=2)
    srv.step()                  # rb: first token read, its last one owed
    assert rb.state == "active" and rb.bucket is None and rb.owed == 1
    faults.configure("dispatch_post:nth=1")
    try:
        with pytest.raises(MXNetError, match="recover"):
            srv.step()
    finally:
        faults.clear()
    assert srv.stats()["poisoned"] and len(rb.generated) == 1
    with pytest.raises(MXNetError, match="recover"):
        srv.settle()                    # latched for every entry
    assert srv.recover() == 2
    assert not srv._owed and [r.id for r in srv.sched.queue] == \
        [ra.id, rb.id]
    srv.run()
    np.testing.assert_array_equal(ra.tokens(), _reference_tokens(lm, pa, 8))
    np.testing.assert_array_equal(rb.tokens(), _reference_tokens(lm, pb, 2))


def test_a_failure_at_the_late_read_poisons_and_names_the_dispatch(
        lm_bucket):
    """(e): a program that fails ON the device says so when its output
    is read, a round after its dispatch returned: the same latch, the
    same event, the dispatch's own name."""
    lm, bucket = lm_bucket
    p = _prompt(99, 5)
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    req = srv.submit(p)
    srv.step()

    class Dead:
        def __array__(self, *a, **k):
            raise RuntimeError("device halted")

    rec, = srv._owed
    assert rec.kind == "decode"
    rec.out = Dead()
    telemetry.clear_events()
    p0 = _counter("mxtpu_poisons_total")
    with pytest.raises(MXNetError, match="recover") as err:
        srv.step()
    assert "device halted" in str(err.value)
    assert srv.stats()["poisoned"]
    assert _counter("mxtpu_poisons_total") - p0 == 1
    ev = telemetry.events("poison")[-1]
    assert ev["where"] == "serving"
    assert ev["name"] == srv.name + "_b%dx%d_decode" % bucket
    with pytest.raises(MXNetError, match="recover"):
        srv.step()
    assert srv.recover() == 1
    srv.run()
    np.testing.assert_array_equal(req.tokens(), _reference_tokens(lm, p, 8))


def test_preemption_drain_reads_what_is_owed(lm_bucket, tmp_path):
    """(e): ``elastic.guardian.drain_server`` records "tokens generated
    so far": with a decode outstanding that includes the owed ones, and
    a request waiting for its last token is finished, not requeued."""
    import json
    from mxnet_tpu.elastic.guardian import drain_server
    lm, bucket = lm_bucket
    srv = Server(lm, buckets=[bucket], max_new_tokens=8)
    ra = srv.submit(_prompt(100, 5))
    rb = srv.submit(_prompt(101, 4), max_new_tokens=2)
    srv.step()
    assert srv._owed and srv.awaiting() == [rb]
    out = drain_server(srv, str(tmp_path))
    assert not srv._owed and rb.state == "done" and len(rb.generated) == 2
    assert (out["requeued"], out["queued"]) == (1, 0)
    row, = json.load(open(out["manifest"]))["requests"]
    assert len(row["generated"]) == 2       # first token + the owed one
    assert ra.state == "queued"


def test_two_buckets_compile_modules_named_by_bucket_and_kind(net):
    """A served program says which bucket it is: a device trace reads
    ``jit_decode_b2x8`` and ``jit_decode_b1x16`` where both buckets'
    closures compiled to ``jit_decode_pure``; the reader of device time
    by scope joins an op to the map of ITS program by that name."""
    srv = Server(net, buckets=[(2, 8), (1, 16)], max_new_tokens=3)
    assert [srv._pure_for(b, kind, k).__name__
            for b in srv.sched.buckets
            for kind, k in (("prefill", 0), ("decode", 0), ("decode", 4))] \
        == ["prefill_b2x8", "decode_b2x8", "decode_b2x8k4",
            "prefill_b1x16", "decode_b1x16", "decode_b1x16k4"]
    short, long_ = srv.submit(_prompt(1, 5)), srv.submit(_prompt(2, 12))
    srv.run()
    assert len(short.generated) == len(long_.generated) == 3
    modules = mx.profiler.device_scopes()
    assert {"jit_prefill_b2x8", "jit_decode_b2x8", "jit_prefill_b1x16",
            "jit_decode_b1x16"} <= set(modules)
    for name in ("jit_prefill_b2x8", "jit_decode_b1x16"):
        assert {"mxtpu.embed", "mxtpu.mixer.full", "mxtpu.mlp",
                "mxtpu.head", "mxtpu.serving.pick"} <= \
            {scope for scope, _bwd, _inh in modules[name].values()}, name
    # the engine's names and persist keys did not move
    assert set(srv._variants) == {"_b2x8_prefill", "_b2x8_decode",
                                  "_b1x16_prefill", "_b1x16_decode"}
