"""The arithmetic that turns what a run recorded into metrics. stdlib only.

Percentiles are by linear interpolation between order statistics (the
"inclusive" method of ``statistics.quantiles``), and always come with the
number of samples they were taken from.
"""
from __future__ import annotations

import bisect
import math


def percentile(values, p: float):
    """(p-th percentile, sample count); (None, 0) of nothing."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        return None, 0
    k = (n - 1) * (p / 100.0)
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo), n


def median(values):
    return percentile(values, 50.0)[0]


def serve_window(records, seconds: float, loop: str) -> dict:
    """Reduce the load generator's per-request records to the window's
    numbers. Times in ``records`` are seconds from the window's start.

    * Open loop: a request belongs to the window when it was DUE inside
      ``[0, seconds)``. Closed loop: when its stream ENDED inside it
      (with long requests, most of what the window completes was sent
      before it opened; what the window's end cut is censored).
    * It FAILED when it ended in any state but FINISHED, ended with fewer
      tokens than it asked for, or (open loop) had not ended when the
      drain time ran out.
    * First-token time counts from when the request was due (open) or sent
      (closed). A failed request is not dropped: it enters the first-token
      times with the window's length, so that shedding load cannot improve
      a tail.
    * Gaps between tokens are those of consecutive tokens of one stream,
      both received inside the window.
    * Tokens are output tokens received inside the window.
    * The longest stall is the longest stretch of the window in which no
      stream at all received a token (its edges count as tokens).
    """
    ttft, gaps, late, finished, connect, accept = [], [], [], [], [], []
    attempted = failed = tokens = 0
    arrivals = [0.0, float(seconds)]
    for r in records:
        tt = r["t_tokens"]
        inside_t = [t for t in tt if 0.0 <= t < seconds]
        tokens += len(inside_t)
        arrivals += inside_t
        gaps += [b - a for a, b in zip(tt, tt[1:])
                 if a >= 0.0 and b < seconds]
        if loop == "open":
            start = r["due_s"]
            inside = 0.0 <= start < seconds
        else:
            start = r.get("sent_s")
            inside = (r.get("error") != "cut"
                      and 0.0 <= r.get("end_s", -1.0) < seconds)
        if not inside:
            continue
        attempted += 1
        ok = (r.get("state") == "FINISHED" and not r.get("error")
              and len(r["tokens"]) == r["asked"])
        if loop == "open":
            late.append(r["sent_s"] - r["due_s"])
        if "connected_s" in r:
            connect.append(r["connected_s"] - r["sent_s"])
        if "headers_s" in r:  # sent -> the 200: the front door's accept path
            accept.append(r["headers_s"] - r["sent_s"])
        if ok:
            ttft.append(tt[0] - start)
            finished.append(r)
        else:
            failed += 1
            ttft.append(float(seconds))
    arrivals.sort()
    return {"attempted": attempted, "failed": failed, "tokens": tokens,
            "ttft_s": ttft, "gaps_s": gaps, "late_s": late,
            "connect_s": connect, "accept_s": accept, "finished": finished,
            "stall_max_s": max(b - a for a, b in zip(arrivals, arrivals[1:]))}


# --- the program's latency histograms (serving/telemetry.py): 96 buckets
# whose upper bounds start at 1 us and grow by 1.25x, one overflow bucket.
# Copied arithmetic: the benchmark reads the raw counts and reduces them
# itself.
BUCKET_BOUNDS = tuple(1e-6 * 1.25 ** i for i in range(96))


def hist_delta(after_counts, before_counts):
    before = before_counts or [0] * len(after_counts)
    return [max(0, a - b) for a, b in zip(after_counts, before)]


def hist_percentile(counts, p: float):
    """(p-th percentile in seconds, samples) of bucket counts, interpolated
    inside the bucket: good to one bucket's width (25%)."""
    total = sum(counts)
    if total <= 0:
        return None, 0
    rank = max(1.0, p / 100.0 * total)
    cum = 0
    for i, c in enumerate(counts):
        if not c:
            continue
        cum += c
        if cum >= rank:
            lo = BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
            hi = (BUCKET_BOUNDS[i] if i < len(BUCKET_BOUNDS)
                  else BUCKET_BOUNDS[-1] * 1.25)
            return lo + (rank - (cum - c)) / c * (hi - lo), total
    return BUCKET_BOUNDS[-1] * 1.25, total


def hist_bucket_of(seconds: float) -> int:
    return bisect.bisect_left(BUCKET_BOUNDS, seconds)
