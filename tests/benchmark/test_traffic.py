"""The one generator: repeats for a seed, differs across seeds, and under
stratified sampling offers every seed the same amount of work."""

import hashlib
import os

import numpy as np
import pytest

from benchmark import harness, traffic

MIX = {"arrival": "poisson", "rate": 0.5,
       "prompt": {"median": 96, "sigma": 0.8, "min": 16, "max": 384},
       "output": {"median": 48, "sigma": 0.6, "min": 8, "max": 128}}


def _draw(seed, sampling, seconds=40):
    mix = dict(MIX, sampling=sampling)
    due = traffic.arrivals(seed, 1, seconds, mix)
    return due, traffic.requests(seed, 1, len(due), mix, 50257)


@pytest.mark.parametrize("sampling", traffic.SAMPLINGS)
def test_same_seed_same_inputs_other_seed_other_inputs(sampling):
    due_a, reqs_a = _draw(7, sampling)
    due_b, reqs_b = _draw(7, sampling)
    due_c, reqs_c = _draw(8, sampling)
    assert np.array_equal(due_a, due_b)
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(reqs_a, reqs_b))
    assert not np.array_equal(due_a[:5], due_c[:5])
    assert not np.array_equal(reqs_a[0][0][:8], reqs_c[0][0][:8])


def test_stratified_offers_every_seed_the_same_work():
    (due_a, reqs_a), (due_b, reqs_b) = _draw(1, "stratified"), \
        _draw(2, "stratified")
    assert len(due_a) == len(due_b) == 20       # round(0.5 * 40)
    assert sorted(len(p) for p, _ in reqs_a) == \
        sorted(len(p) for p, _ in reqs_b)
    assert sorted(o for _, o in reqs_a) == sorted(o for _, o in reqs_b)
    for due in (due_a, due_b):
        assert np.all(np.diff(due) > 0) and 0 <= due[0] and due[-1] < 40


def test_lengths_are_a_clipped_lognormal_given_by_its_median():
    rng = np.random.RandomState(0)
    lens = traffic.lognormal_lengths(rng, 2001, MIX["prompt"], "stratified")
    assert lens.min() == 16 and lens.max() == 384
    assert abs(np.median(lens) - 96) <= 1
    # ln(len) has the sigma asked for, in the part the clip leaves alone
    z = np.log(np.sort(lens)[400:1600] / 96.0)
    assert abs(z.std() - 0.8 * 0.4632) < 0.02   # std of a normal's middle 60%


def test_iid_poisson_rate():
    rng = np.random.RandomState(3)
    due = traffic.poisson_arrivals(rng, 5.0, 400.0, "iid")
    assert abs(len(due) / 400.0 - 5.0) < 0.4


def test_tokens_are_uniform_not_a_tiled_phrase():
    _, reqs = _draw(5, "iid")
    prompt = max((p for p, _ in reqs), key=len)
    assert len(set(prompt.tolist())) > 0.9 * len(prompt)


def test_training_batches_are_distinct_and_seeded():
    a = traffic.token_batches(1, 4, 2, 16, 1000)
    assert a.shape == (4, 2, 16) and a.dtype == np.int32
    assert np.array_equal(a, traffic.token_batches(1, 4, 2, 16, 1000))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a, traffic.token_batches(2, 4, 2, 16, 1000))


def test_unknown_sampling_is_refused():
    with pytest.raises(ValueError, match="unknown sampling"):
        traffic.lognormal_lengths(np.random.RandomState(0), 4,
                                  MIX["prompt"], "sobol")


def test_a_fixed_schedule_is_replayed_with_seeded_jitter_and_new_tokens():
    mix = dict(MIX, sampling="stratified", schedule_seed=2,
               arrival_jitter_s=0.1)
    due_a = traffic.arrivals(1, 1, 40, mix)
    due_b = traffic.arrivals(9, 1, 40, mix)
    plain = traffic.arrivals(123, 1, 40, dict(mix, arrival_jitter_s=0.0))
    assert len(due_a) == len(due_b) == len(plain)
    assert not np.array_equal(due_a, due_b)
    for due in (due_a, due_b):
        assert np.all(np.diff(due) >= 0)
        assert np.all(np.abs(np.sort(due) - plain) <= 0.1 + 1e-9)
    reqs_a = traffic.requests(1, 1, len(due_a), mix, 50257)
    reqs_b = traffic.requests(9, 1, len(due_b), mix, 50257)
    assert [(len(p), o) for p, o in reqs_a] == \
        [(len(p), o) for p, o in reqs_b]
    assert not np.array_equal(reqs_a[0][0], reqs_b[0][0])


def _mix(name):
    return harness.load_json(os.path.join(
        harness.ROOT, "benchmark/workloads", name + ".json"))


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()


def _requests_digest(reqs):
    return _digest(*[p for p, _ in reqs],
                   np.asarray([o for _, o in reqs], np.int64))


@pytest.mark.parametrize("seed", range(4))
def test_the_four_mixes_draw_what_they_drew_before_sources_had_names(seed):
    """``traffic_recorded.json`` holds digests of what the generator gave
    for the benchmark's four mixes, called as the drivers call it, at the
    commit before token sources and arrival processes were found by name
    (PR 26): ``uniform`` and ``poisson`` are the same draws, so no cell's
    inputs moved. ``chat-loaded`` took ``chat-open``'s place in PR 32 (a
    rate some 35 times higher, a fresh schedule): its three streams were
    recorded then."""
    want = harness.load_json(os.path.join(
        harness.ROOT, "tests/benchmark/traffic_recorded.json"))[str(seed)]
    vocab = 50257
    closed = _mix("decode-closed")
    reqs = traffic.requests(seed, 1, closed["request_pool"], closed, vocab)
    assert _requests_digest(reqs) == want["decode-closed"]["requests"]
    assert reqs[0][0][:4].tolist() == \
        want["decode-closed"]["first_prompt_head"]
    chat = _mix("chat-loaded")
    for stream, seconds in ((0, chat["warmup_s"]), (1, 51),
                            (2, chat["trace_tail_s"])):
        due = traffic.arrivals(seed, stream, seconds, chat)
        recorded = want["chat-loaded"][str(stream)]
        assert len(due) == recorded["n"] and due[0] == recorded["first_due"]
        assert _digest(np.asarray(due, np.float64)) == recorded["arrivals"]
        assert _requests_digest(traffic.requests(
            seed, stream, len(due), chat, vocab)) == recorded["requests"]
    for name, chips in (("pretrain-t1024-b16", 1),
                        ("pretrain-t1024-b4-dp4", 4)):
        train = _mix(name)
        batches = traffic.token_batches(
            seed, 2, train["batch_per_chip"] * chips, train["seq_len"],
            vocab, train)
        assert _digest(batches) == want[name]["batches"]
        assert batches[0, 0, :4].tolist() == want[name]["head"]


@pytest.mark.parametrize("stream,at_least", [(1, 300), (2, 16)])
def test_the_loaded_chat_mix_fills_its_window_alike_for_every_seed(
        stream, at_least):
    """The open cell's median is over hundreds of requests (17 made it
    noise, PR 32), its traced tail holds enough for the request readers,
    and every seed is offered the same multiset of lengths."""
    chat = _mix("chat-loaded")
    seconds = {1: 51, 2: chat["trace_tail_s"]}[stream]
    drawn = []
    for seed in (0, 5, 2147483999):
        due = traffic.arrivals(seed, stream, seconds, chat)
        reqs = traffic.requests(seed, stream, len(due), chat, 50257)
        assert np.all(np.diff(due) >= 0) and due[0] >= 0
        drawn.append((len(due), sorted(len(p) for p, _ in reqs),
                      sorted(o for _, o in reqs), reqs[0][0][:8].tolist()))
    assert drawn[0][0] >= at_least
    assert drawn[0][:3] == drawn[1][:3] == drawn[2][:3]
    assert drawn[0][3] != drawn[1][3]
    # the replay's jitter stays under one mean gap
    assert chat["arrival_jitter_s"] < 1.0 / chat["rate"]


def test_an_unknown_source_is_refused():
    with pytest.raises(ValueError, match="unknown token source or arrival"):
        traffic.arrivals(1, 1, 10, dict(MIX, arrival="no_such_process"))
