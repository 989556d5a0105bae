"""``mxnet_tpu.serving``: the production inference serving plane
(ROADMAP item 4 — the "millions of users" leg).

Whole-program AOT compilation to FIXED shapes is the regime TPUs
reward (arXiv:1810.09868), and the compile-once/serve-forever
deployment story follows Relay's ahead-of-time philosophy
(arXiv:1810.00952).  This package turns the model zoo's
prefill/decode seams into that story:

* :mod:`~.kvcache` — preallocated per-slot K/V pages as DONATED carry
  state: every decode dispatch updates the caches in place and
  round-trips the buffers, with the PR 2/3 poison/recover protocol;
* :mod:`~.scheduler` — continuous batching over fixed
  ``(slots, prompt_len)`` buckets: admits and evicts swap slot
  contents and an active-mask input, NEVER shapes, so steady state
  retraces nothing;
* :mod:`~.server` — ``Server``: one compiled decode program per bucket
  (plus scan-bulked ``decode_multi``) and a ladder of compiled prefill
  programs (an admission runs the shortest that holds its prompt),
  greedy/temperature/top-k sampling with the CachedOp fold_in RNG
  scheme, ``save_signature``/``warm_start`` through the PR 5
  persistent tier (a fresh process serves its first token with 0
  fresh compiles), and the serving telemetry (tokens/sec, TTFT,
  per-request latency, occupancy, queue depth,
  ``request_evicted``/``slot_oom`` retained events).

See docs/serving.md for the bucket anatomy, a scheduler walkthrough,
the warm-start workflow, and the telemetry field reference.
"""
from .kvcache import KVCachePool
from .scheduler import Bucket, BucketScheduler, Request, prefill_ladder
from .server import Server, servers

__all__ = ["KVCachePool", "Bucket", "BucketScheduler", "Request",
           "Server", "servers", "prefill_ladder"]
