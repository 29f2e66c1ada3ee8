"""The one traffic generator: every mix is a data file of parameters
(``benchmark/workloads/<traffic>.json``) that these functions read.

Arithmetic copied from ``deepspeed_tpu/loadgen/workload.py`` (seeded
exponential gaps, clipped lognormal lengths), with three differences: a
lognormal is given by its MEDIAN, tokens are uniform over the vocabulary
(no tiled phrase that would flatter an n-gram drafter), and a mix may ask for
``"sampling": "stratified"``: the lengths and gaps of a window are then the
n evenly spaced quantiles of their distributions in an order drawn from the
seed, so that every seed offers the same amount of work and runs differ by
order and content only. ``"iid"`` draws each value independently.

A mix may also fix its SCHEDULE: with ``"schedule_seed"`` the lengths and the
due times are drawn from that number, the same in every run, and ``--seed``
draws only the tokens (and the weights) and moves each due time by up to
``"arrival_jitter_s"``. That is a recorded trace replayed with a little
jitter: what a cell needs whose window holds too few requests for a median
over freshly drawn arrivals to repeat (PERF.md section 4).

Where the tokens and the due times come from is found by NAME: a mix may
give ``"tokens"`` (default ``uniform``) and ``"arrival"`` (default
``poisson``), each a file ``traffic_sources/<name>.py`` under ``paths``. A
token source has ``prompts(rng, lengths, vocab_size, mix)`` and ``batches(rng,
n, batch, seq_len, vocab_size, mix)`` (whichever its kind of traffic draws),
an arrival process ``arrivals(rng, rate, seconds, sampling, mix)``; each is
handed the generator this module seeded, so the same ``--seed`` gives the
same inputs. A later PR adds a source as a file (benchmark/README.md).
"""

import math
from statistics import NormalDist

import numpy as np

SAMPLINGS = ("stratified", "iid")


def _uniforms(rng, n, sampling):
    if sampling == "stratified":
        return rng.permutation((np.arange(n) + 0.5) / n)
    if sampling == "iid":
        return rng.uniform(size=n)
    raise ValueError("unknown sampling {!r}; one of {}".format(
        sampling, SAMPLINGS))


def lognormal_lengths(rng, n, spec, sampling):
    """``n`` whole lengths from ``spec`` = {"median", "sigma", "min",
    "max"}: exp(ln(median) + sigma * z), rounded, clipped to [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if lo < 1 or hi < lo:
        raise ValueError("length bounds must satisfy 1 <= min <= max, got "
                         "{}".format(spec))
    inv = NormalDist().inv_cdf
    z = np.array([inv(u) for u in _uniforms(rng, n, sampling)])
    lens = np.exp(math.log(float(spec["median"])) + float(spec["sigma"]) * z)
    return np.clip(np.rint(lens), lo, hi).astype(int)


def poisson_arrivals(rng, rate, seconds, sampling):
    """Due times in [0, seconds) of a Poisson process of ``rate`` a second.
    Stratified: round(rate * seconds) exponential gaps at evenly spaced
    quantiles, in a seeded order, scaled to fill the window exactly."""
    if sampling == "stratified":
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-_uniforms(rng, n, sampling))
        times = np.cumsum(gaps)
        return (times - gaps[0] * rng.uniform()) * (seconds / times[-1])
    times, t = [], rng.exponential(1.0 / rate)
    while t < seconds:
        times.append(t)
        t += rng.exponential(1.0 / rate)
    return np.asarray(times)


def source(name):
    """The token source or arrival process ``name``: the module
    ``traffic_sources/<name>.py`` under ``paths``."""
    from benchmark import harness

    try:
        return harness.load_by_name("traffic_sources", name)
    except FileNotFoundError as e:
        raise ValueError("unknown token source or arrival process {!r}: {}"
                         .format(name, e))


def requests(seed, stream, n, traffic, vocab_size):
    """``n`` requests of a serving mix: a list of (prompt tokens int32,
    max_new_tokens). ``stream`` separates the warm-up, the window and the
    traced tail of one seed."""
    schedule = traffic.get("schedule_seed", seed)
    rng = np.random.RandomState([int(schedule), int(stream), 1])
    sampling = traffic.get("sampling", "iid")
    p_lens = lognormal_lengths(rng, n, traffic["prompt"], sampling)
    o_lens = lognormal_lengths(rng, n, traffic["output"], sampling)
    prompts = source(traffic.get("tokens", "uniform")).prompts(
        np.random.RandomState([int(seed), int(stream), 4]), p_lens,
        vocab_size, traffic)
    return [(np.asarray(p, np.int32), int(o))
            for p, o in zip(prompts, o_lens)]


def arrivals(seed, stream, seconds, traffic):
    """Due times of an open-loop mix over ``seconds`` seconds."""
    schedule = traffic.get("schedule_seed", seed)
    rng = np.random.RandomState([int(schedule), int(stream), 2])
    due = np.asarray(source(traffic.get("arrival", "poisson")).arrivals(
        rng, float(traffic["rate"]), float(seconds),
        traffic.get("sampling", "iid"), traffic), np.float64)
    jitter = float(traffic.get("arrival_jitter_s", 0.0))
    if jitter:
        due = np.sort(due + np.random.RandomState(
            [int(seed), int(stream), 5]).uniform(0.0, jitter, size=len(due)))
    return due


def token_batches(seed, n, batch, seq_len, vocab_size, traffic=None):
    """``n`` distinct training batches ``[n, batch, seq_len]`` of tokens
    from the source the mix ``traffic`` names (uniform without one)."""
    traffic = traffic or {}
    return np.asarray(source(traffic.get("tokens", "uniform")).batches(
        np.random.RandomState([int(seed), 3]), n, batch, seq_len,
        vocab_size, traffic), np.int32)
