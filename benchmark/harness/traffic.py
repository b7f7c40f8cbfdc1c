"""The one traffic generator: a mix's data file plus a seed give the whole
schedule (when each request is due, its prompt, how many tokens it asks
for) before the window opens. numpy only; the program sees only requests.

A mix file (``benchmark/traffic/<mix>.json``) has::

    {"loop": "open" | "closed" | "steps",
     "rate_per_s": 5.2,            # open: Poisson arrivals, gaps ~ Exp(rate)
     "gap_cv": 1.0,                # open: 1 = Poisson; >1 = gamma gaps, bursts
     "clients": 64,                # closed: each sends its next when one ends
     "shuffle_block": 64,          # closed: a seed reorders requests only
                                   # inside consecutive blocks of this many
     "prompt": {"median": 768, "sigma": 0.5, "min": 256, "max": 1536},
     "output": {"median": 384, "sigma": 0.4, "min": 128, "max": 512},
     "shared_prefix": 0,           # tokens every prompt starts with
     "ramp_s": 10,                 # load offered before the window opens
     "drain_s": 15,                # open: wait this long for stragglers
     "check_sample": 3}            # finished requests the reference re-runs

Every seed gets the same multiset of lengths and gaps in another order
(the draws come from a generator seeded by the mix, the order from
``--seed``), so a seed changes which request meets which, not how much
work a run holds. A closed loop's window consumes only the head of its
list, so there ``shuffle_block`` keeps every (prompt, output) pair inside
its block of the list: the first cohort is the same set of requests for
every seed, and so is each block after it. Prompt tokens come from
``--seed``.
"""
from __future__ import annotations

import hashlib
import math

import numpy as np


def _mix_rng(mix: dict, what: str) -> np.random.Generator:
    key = hashlib.sha256((what + repr(sorted(
        (k, repr(v)) for k, v in mix.items()))).encode()).digest()
    return np.random.default_rng(int.from_bytes(key[:8], "little"))


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    """Lognormal around ``median``, clipped to [min, max]."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, mix: dict, n: int) -> np.ndarray:
    mean = 1.0 / float(mix["rate_per_s"])
    cv = float(mix.get("gap_cv", 1.0))
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, mean / shape, n)


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """Requests for one run, without their prompt tokens (see
    :func:`prompt_tokens`): small enough to hand to the load generator as
    a file. ``due_s`` is relative to the window's start (negative inside
    the ramp). A closed loop has no due times: whichever client is free
    takes the next request of the list, which holds
    ``requests_per_client`` for each."""
    order = np.random.default_rng(int(seed))
    ramp = float(mix.get("ramp_s", 0))
    if mix["loop"] == "open":
        # the same gaps for every seed, in another order, scaled to end
        # with the window: a seed never changes how much is offered
        n = int(round((ramp + seconds) * mix["rate_per_s"]))
        gaps = _gaps(_mix_rng(mix, "gaps"), mix, n)
        gaps = order.permutation(gaps * ((ramp + seconds) / gaps.sum()))
        due = np.cumsum(gaps) - gaps[0] * 0.5 - ramp
    elif mix["loop"] == "closed":
        n = int(mix["clients"]) * int(mix.get("requests_per_client", 12))
        due = None
    else:
        raise ValueError(f"loop {mix['loop']!r} has no requests")
    plen = _lengths(_mix_rng(mix, "prompt"), mix["prompt"], n)
    olen = _lengths(_mix_rng(mix, "output"), mix["output"], n)
    block = int(mix.get("shuffle_block", 0))
    if block:
        idx = np.concatenate([a + order.permutation(min(block, n - a))
                              for a in range(0, n, block)])
        plen, olen = plen[idx], olen[idx]
    else:
        plen, olen = order.permutation(plen), order.permutation(olen)
    out = {"loop": mix["loop"], "seed": int(seed), "vocab": int(vocab),
           "shared_prefix": int(mix.get("shared_prefix", 0)),
           "ramp_s": ramp, "seconds": float(seconds),
           "drain_s": float(mix.get("drain_s", 0)),
           "requests": [{"id": i, "prompt_len": int(plen[i]),
                         "max_new_tokens": int(olen[i]),
                         "due_s": None if due is None else float(due[i])}
                        for i in range(n)]}
    if mix["loop"] == "closed":
        out["clients"] = int(mix["clients"])
    return out


def prompt_tokens(sched: dict, req: dict) -> list:
    """The prompt of one scheduled request, from the run's seed and the
    request's number alone, so that the load generator and the check make
    the same tokens without passing them around."""
    seed, vocab = sched["seed"], sched["vocab"]
    shared = min(sched["shared_prefix"], req["prompt_len"])
    prefix = np.random.default_rng([seed, 0]).integers(0, vocab, shared)
    body = np.random.default_rng([seed, 1, req["id"]]).integers(
        0, vocab, req["prompt_len"] - shared)
    return np.concatenate([prefix, body]).astype(np.int64).tolist()


def train_batches(mix: dict, seed: int, batch: int, seq: int, vocab: int):
    """An endless feed of fresh (ids, labels) int32 batches [batch, seq]
    from the seed: every row differs, no batch repeats. Labels are the
    ids shifted left by one (the last position predicts the first)."""
    rng = np.random.default_rng(int(seed))
    while True:
        ids = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        yield ids, np.roll(ids, -1, axis=1)
