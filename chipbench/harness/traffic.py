"""The one general traffic generator: it reads a traffic file's
parameters and draws lengths, arrivals and token ids from ``--seed``.

Every seed gets the SAME multiset of sizes and gaps in another order: a
distribution is cut into ``block`` equally likely strata, one value from
each, and the seed only permutes a block.  So two seeds offer the same
work, and a run differs from another by order alone, not by having drawn
a heavier tail.
"""
import math
import statistics

import numpy as np

# samples behind the quantiles of a distribution that has no closed form
# here; its seed is fixed because it defines the distribution, not a run
_TABLE_SEED = 20260928
_TABLE_SIZE = 200_000


def rng_for(seed, stream):
    """Independent generators for one run: ``stream`` keeps lengths,
    arrivals, tokens and weights apart.  Any whole ``seed`` is fine."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def strata(dist, n):
    """``n`` values, the quantiles (i + 0.5) / n of ``dist``.

    ``dist["kind"]``: ``fixed`` (value), ``uniform`` (low, high),
    ``exponential`` (mean), ``lognormal`` (median, sigma), or the name of
    any ``numpy.random.Generator`` method with its arguments under
    ``args`` (``gamma``, ``weibull``, ``pareto``...), whose quantiles come
    from a fixed table of samples.  Optional ``min``/``max`` clip, and
    ``round`` makes whole numbers."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "fixed":
        v = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        v = dist["low"] + (dist["high"] - dist["low"]) * q
    elif kind == "exponential":
        v = -dist.get("mean", 1.0) * np.log1p(-q)
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        table = getattr(np.random.default_rng(_TABLE_SEED), kind)(
            size=_TABLE_SIZE, **dist.get("args", {}))
        v = np.quantile(table, q) * dist.get("scale_by", 1.0)
    if "min" in dist or "max" in dist:
        v = np.clip(v, dist.get("min", -np.inf), dist.get("max", np.inf))
    if dist.get("round"):
        v = np.rint(v)
    return v


def permuted_blocks(values, rng):
    """Endless stream over ``values``: block after block, each a fresh
    permutation of the same values."""
    while True:
        for i in rng.permutation(len(values)):
            yield values[i]


def arrival_times(rate_per_s, gaps, block, rng, t_from, t_to):
    """Arrival times in [t_from, t_to) at a mean rate ``rate_per_s``.
    ``gaps`` is a distribution of mean 1 (``exponential`` is Poisson);
    its strata are scaled by 1 / rate and permuted per block."""
    g = strata(gaps, block)
    g = g / g.mean() / rate_per_s
    out, t = [], t_from
    for gap in permuted_blocks(g, rng):
        t += gap
        if t >= t_to:
            return out
        out.append(t)


def request_stream(traffic, vocab, seed):
    """Endless (prompt tokens, new tokens asked) from the traffic file's
    ``prompt_len`` and ``output_len``; prompts are uniform token ids."""
    n = int(traffic["length_block"])
    plen = permuted_blocks(strata(traffic["prompt_len"], n), rng_for(seed, 1))
    olen = permuted_blocks(strata(traffic["output_len"], n), rng_for(seed, 2))
    toks = rng_for(seed, 3)
    while True:
        p = int(next(plen))
        yield toks.integers(1, vocab, p).astype(np.float32), int(next(olen))


def zipf_cdf(vocab, exponent):
    w = 1.0 / np.arange(1, vocab + 1) ** exponent
    return np.cumsum(w / w.sum())


def bert_batch(rng, cdf, batch, seq, masked):
    """One MLM+NSP pretraining batch as the trainer takes it: (tokens,
    segment ids, masked positions), and labels = the true tokens at those
    positions followed by a random next-sentence label.  Token ids follow
    ``cdf`` (Zipf over the vocabulary, rank = id)."""
    tokens = np.minimum(np.searchsorted(cdf, rng.random((batch, seq))),
                        len(cdf) - 1)
    split = rng.integers(1, seq, (batch, 1))
    types = (np.arange(seq)[None, :] >= split)
    pos = np.argsort(rng.random((batch, seq)), axis=1)[:, :masked]
    pos.sort(axis=1)
    mlm = np.take_along_axis(tokens, pos, axis=1)
    nsp = rng.integers(0, 2, (batch, 1))
    f = np.float32
    return ((tokens.astype(f), types.astype(f), pos.astype(f)),
            np.concatenate([mlm, nsp], axis=1).astype(f))
