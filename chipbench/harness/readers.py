"""Reductions that several metric files share.  A metric's own file says
WHAT it reads; how is here once.  Each returns None when ``obs`` has
nothing to read, and the metric is then left out of the line."""
from . import stats


def window_span_ms(obs, name):
    """Median length, in ms, of the benchmark's host span ``name`` over
    the spans that lie wholly inside the measured window."""
    t0, t1 = obs["window"]
    d = obs["spans"].durations(name, t0, t1)
    return stats.median(d) * 1e3 if d else None


def idle_share(obs):
    """1 - union of device-op intervals / traced span, device 0, in %."""
    tr = obs.get("trace")
    return None if tr is None else tr["idle_share_dev0"] * 100.0


def first_call_seconds(obs):
    """Set-up seconds spent in the first call of each program."""
    s = [v for k, v in obs["setup"].items() if k.startswith("first_")]
    return sum(s) if s else None


def decode_round_ms(obs):
    """Median wall time of the ``Server.step()`` rounds in the window
    that admitted nothing: pure decode, one dispatch per busy bucket."""
    t0, t1 = obs["window"]
    d = [r["t1"] - r["t0"] for r in obs.get("rounds", ())
         if r["admitted"] == 0 and t0 <= r["t0"] and r["t1"] <= t1]
    return stats.median(d) * 1e3 if d else None


def counted(obs):
    return [r for r in obs.get("requests", ()) if r["counted"]]


def first_token_waits(obs):
    """Per counted request: first token's arrival minus the DUE time.  A
    request that never got a token waited at least until the run gave up."""
    return [(r["stamps"][0] if r["stamps"] else obs["t_end"]) - r["due"]
            for r in counted(obs)]


def inter_token_gaps(obs):
    """Every gap between consecutive tokens of the counted requests."""
    return [g for r in counted(obs) for g in stats.token_gaps(r["stamps"])]


def generator_lateness(obs):
    return [r["submit"] - r["due"] for r in counted(obs)]


def tokens_in_window(obs):
    """Output tokens of ANY request that arrived inside the window."""
    t0, t1 = obs["window"]
    return sum(stats.in_window(r["stamps"], t0, t1) for r in obs["requests"])


STEADY_CHUNK = 25     # rounds to a chunk: about a second of serving


def steady_tokens_per_s(obs):
    """Median, over chunks of ``STEADY_CHUNK`` consecutive rounds inside
    the window, of the chunk's output tokens over its time (first
    round's start to the next chunk's).  The rate the server holds while
    nothing holds the process up: a statistic BESIDE
    ``serve_tokens_per_s``, which is over all the work and all the time
    of the window."""
    t0, t1 = obs["window"]
    rounds = [r for r in obs.get("rounds", ())
              if t0 <= r["t0"] and r["t1"] <= t1]
    starts = range(0, len(rounds) - STEADY_CHUNK, STEADY_CHUNK)
    rates = [sum(r["tokens"] + r["admitted"]
                 for r in rounds[i:i + STEADY_CHUNK])
             / (rounds[i + STEADY_CHUNK]["t0"] - rounds[i]["t0"])
             for i in starts]
    return stats.median(rates)
