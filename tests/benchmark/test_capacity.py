"""``benchmark/capacity.py``'s loop, driven by a stand-in engine: the queue
is kept full and never past ``max_queue``."""

import collections

import pytest

from benchmark import capacity


class _Handle(object):
    def __init__(self, max_new):
        self.max_new, self.tokens = max_new, []
        self.admit_time = None

    @property
    def done(self):
        return len(self.tokens) >= self.max_new


class _Engine(object):
    """Admits ONE queued request a step into a free slot (as the one prefill
    lane does) and gives every admitted request ``chunk`` tokens a step;
    refuses, as the scheduler does, past ``max_queue`` waiting."""

    def __init__(self, slots, max_queue, expects, chunk=4):
        self.slots, self.max_queue, self.chunk = slots, max_queue, chunk
        self.left = expects
        self.queue, self.running = collections.deque(), []
        self.deepest = self.steps = self.starved = 0

    def submit(self, prompt, max_new_tokens):
        assert len(self.queue) < self.max_queue, "QueueFull"
        self.queue.append(_Handle(max_new_tokens))
        self.left -= 1
        self.deepest = max(self.deepest, len(self.queue))
        return self.queue[-1]

    def step(self):
        self.steps += 1
        if len(self.running) < self.slots:
            if self.queue:
                self.queue[0].admit_time = self.steps
                self.running.append(self.queue.popleft())
            elif self.left:
                self.starved += 1
        for h in self.running:
            h.tokens += [0] * min(self.chunk, h.max_new - len(h.tokens))
        self.running = [h for h in self.running if not h.done]


@pytest.mark.parametrize("slots,max_queue,n", [(4, 8, 100), (2, 3, 40),
                                               (16, 64, 600)])
def test_the_queue_is_kept_full_and_never_past_max_queue(slots, max_queue, n):
    reqs = [([1, 2, 3], 5 + (7 * i) % 23) for i in range(n)]
    engine = _Engine(slots, max_queue, n)
    handles, steps, seconds, most = capacity.serve_topped_up(
        engine, reqs, max_queue)
    assert len(handles) == n and all(h.done for h in handles)
    assert [len(h.tokens) for h in handles] == [o for _, o in reqs]
    assert steps == engine.steps and seconds > 0
    # full, never fuller: the engine would have refused one more
    assert most == engine.deepest == max_queue
    # and never short of work while requests were left to hand over
    assert engine.starved == 0
