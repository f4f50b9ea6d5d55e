"""The one general traffic generator: a traffic file's parameters and a
seed give the requests or the rows of a run.

Stratified, not sampled: lengths and gaps are the (i + 1/2)/N quantiles of
their distributions, laid out once in one fixed order; the seed only
rotates that cycle (and draws the token ids).  So every seed offers the
same multiset of prompt lengths, output lengths and gaps, hence the same
load, with the same neighbours, from another starting point.  PR 23's raw
Poisson draw varied the offered load by 1/sqrt(N) from seed to seed, which
at four fifths of the knee carried some seeds past saturation; permuting
the lists afresh for every seed read 2.5 % on `tpot_p90_ms` where
rotating reads 0.5 % (PERF.md section 6).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class PlannedRequest:
    due_s: float          # offset from the window's start (serve-open)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    scored: bool = True
    client: int = -1      # serve-closed: which client sends it


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    if kind == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * u
    if kind == "fixed":
        return dist["value"]
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified_lengths(dist: dict, n: int) -> list[int]:
    """``n`` whole lengths: the mid-stratum quantiles, clipped."""
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return [
        int(min(hi, max(lo, round(_quantile(dist, (i + 0.5) / n))))) for i in range(n)
    ]


def stratified_gaps(arrivals: dict, n: int, span_s: float) -> list[float]:
    """``n`` inter-arrival gaps that sum to ``span_s``.  ``exponential``:
    the mid-stratum quantiles of an exponential, scaled.  ``bursty``: the
    same quantiles for the gaps between bursts of ``burst`` requests, the
    requests of one burst ``burst_gap_s`` apart."""
    kind = arrivals.get("gaps", "exponential")
    if kind == "exponential":
        raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        scale = span_s / sum(raw)
        return [g * scale for g in raw]
    if kind == "bursty":
        burst = int(arrivals["burst"])
        inner = float(arrivals.get("burst_gap_s", 0.0))
        groups = math.ceil(n / burst)
        raw = [-math.log(1.0 - (i + 0.5) / groups) for i in range(groups)]
        within = (n - groups) * inner
        scale = max(span_s - within, 0.0) / sum(raw)
        gaps = []
        for g in raw:
            gaps.append(g * scale)
            gaps.extend([inner] * (burst - 1))
        return gaps[:n]
    raise ValueError(f"unknown arrival pattern {kind!r}")


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def ordered(values: list, seed: int, stream: int, unit: int = 1) -> list:
    """``values`` (in quantile order) in the order this seed sends them:
    one permutation, the same for every seed, lays the list out, and the
    seed rotates it (by the same offset for every list of the mix, so that
    lengths and gaps stay paired; in whole ``unit``s, a closed loop's
    clients).  Every seed replays the same cycle of requests from another
    starting point: who arrives beside whom is the same, and only the ends
    of the window differ."""
    n = len(values)
    laid = [values[i] for i in _rng(0, stream).permutation(n)]
    k = unit * int(_rng(seed, 0).integers(0, n // unit))
    return laid[k:] + laid[:k]


def open_loop(traffic: dict, seed: int, span_s: float, vocab: int) -> list[PlannedRequest]:
    """Requests of a ``serve-open`` mix due in ``[0, span_s)``, in due
    order, ``rate_per_s * span_s`` of them.  The scored ones are one whole
    stratified set of N, the same multiset for every seed; ahead of them
    goes a warm-up of ``warm_share * N`` more that is sent and not scored
    (the end of the same cycle, so the window opens on a system in use)."""
    warm_share = traffic.get("warm_share", 0.1)
    n = max(1, round(traffic["rate_per_s"] * span_s / (1.0 + warm_share)))
    n_warm = round(warm_share * n)
    prompts = ordered(stratified_lengths(traffic["prompt_tokens"], n), seed, 1)
    outputs = ordered(stratified_lengths(traffic["output_tokens"], n), seed, 2)
    gaps = ordered(stratified_gaps(traffic.get("arrivals", {}), n, 1.0), seed, 3)
    lead = list(range(n - n_warm, n))
    prompts = [prompts[i] for i in lead] + prompts
    outputs = [outputs[i] for i in lead] + outputs
    gaps = [gaps[i] for i in lead] + gaps
    scale = span_s / sum(gaps)
    gaps = [g * scale for g in gaps]
    ids = _rng(seed, 4)
    sessions = traffic.get("sessions")
    shared = None
    if sessions:
        # every session's turns open with one shared prefix per session
        n_sessions = math.ceil((n + n_warm) / sessions["turns"])
        shared = [
            ids.integers(0, vocab, sessions["shared_prefix_tokens"], dtype=np.int32)
            for _ in range(n_sessions)
        ]
    out, t = [], 0.0
    for i in range(n + n_warm):
        t += gaps[i]
        body = ids.integers(0, vocab, prompts[i], dtype=np.int32)
        if shared is not None:
            body = np.concatenate([shared[i // sessions["turns"]], body])
        # the last gap lands on span_s itself; keep every due time inside
        due = min(t - gaps[i] / 2, span_s)
        out.append(PlannedRequest(due, body, outputs[i], scored=i >= n_warm))
    return out


def closed_loop(traffic: dict, seed: int, vocab: int) -> list[list[PlannedRequest]]:
    """Per client, the requests it sends one after another.  A client's
    first request stands for one caught in flight: ``phase`` of its answer
    (the clients' phases are stratified over [0, 1)) is already part of the
    prompt, so the cache holds what it would mid-answer and the window
    opens on a steady state, not on a cohort that finishes together."""
    clients, per = int(traffic["clients"]), int(traffic["requests_per_client"])
    n = clients * per
    prompts = stratified_lengths(traffic["prompt_tokens"], n)
    outputs = stratified_lengths(traffic["output_tokens"], n)
    prompts = ordered(prompts, seed, 1, unit=per)
    outputs = ordered(outputs, seed, 2, unit=per)
    phases = ordered([(c + 0.5) / clients for c in range(clients)], seed, 3)
    ids = _rng(seed, 4)
    plan = []
    for c in range(clients):
        mine = []
        for j in range(per):
            p_len, o_len = prompts[c * per + j], outputs[c * per + j]
            if j == 0:
                done = min(o_len - 1, int(phases[c] * o_len))
                p_len, o_len = p_len + done, o_len - done
            mine.append(PlannedRequest(
                0.0, ids.integers(0, vocab, p_len, dtype=np.int32), o_len, client=c,
            ))
        plan.append(mine)
    return plan


def token_rows(traffic: dict, seed: int, rows: int, vocab: int) -> np.ndarray:
    """``rows`` sequences of ``seq_len`` ids from a seeded stream whose
    unigram frequencies fall off as a Zipf law over a seeded permutation of
    the vocabulary: rows all differ, and there is something to learn."""
    rng = _rng(seed, 5)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / (ranks + traffic.get("zipf_offset", 10.0)) ** traffic.get("zipf_s", 1.1)
    cdf = np.cumsum(p / p.sum())
    order = rng.permutation(vocab).astype(np.int32)
    u = rng.random((rows, int(traffic["seq_len"])))
    return order[np.minimum(np.searchsorted(cdf, u), vocab - 1)]
