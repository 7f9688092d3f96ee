"""The one traffic generator.  A mix is a data file under
``benchmarks/traffic/``; this module turns it and a seed into requests.

Closed-loop serving mixes (``"loop": "closed"``):

    {"loop": "closed", "clients": 32, "requests": 96,
     "prompt_len": [128, 256], "new_tokens": [128, 384],
     "warm": [[128, 2], [256, 2]], "check_requests": 3}

fix a **multiset** of (prompt length, new tokens) pairs: ``requests``
pairs whose prompt lengths and new-token counts are each spaced evenly
over their range, paired by a fixed stride so that long prompts meet
short and long answers alike.  The seed decides the order in which the
pairs are sent and the token ids, nothing else: every seed offers the
same work.  ``warm`` lists the requests that are run alone before the
ramp, so that every prefill and decode program the mix reaches has run
before the window: the ramp itself is short and stays in the first ones.

Training mixes (``"loop": "train"``) fix the global batch, the number
of batches held on the host, and the steps run before the window opens.
"""

from __future__ import annotations

import math
import threading

import numpy as np


def _evenly(lo: int, hi: int, n: int) -> list:
    if n == 1 or lo == hi:
        return [int(round((lo + hi) / 2))] * n
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]


def _stride(n: int) -> int:
    """A stride near n / golden ratio that is coprime to n, so that
    ``i -> i * stride mod n`` visits every index once."""
    s = max(1, int(round(n * 0.6180339887)))
    while math.gcd(s, n) != 1:
        s += 1
    return s


def multiset(mix: dict) -> list:
    """The mix's (prompt length, new tokens) pairs, in a fixed order that
    does not depend on any seed."""
    n = int(mix["requests"])
    prompts = _evenly(*mix["prompt_len"], n)
    news = _evenly(*mix["new_tokens"], n)
    s = _stride(n)
    return [(prompts[i], news[(i * s) % n]) for i in range(n)]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number up
    to a little over 2**31 and beyond: SeedSequence takes them all)."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


class ClosedLoopPlan:
    """What the clients of one run send, in order.

    ``ramp[i]`` is client i's first request: the i-th pair of the order,
    cut to ceil(shortest answer * (i + 1) / clients) new tokens, so that
    no two clients leave the ramp in the same decode step and the ramp
    lasts as long as the mix's shortest answer.  After the ramp the
    clients draw from ``order`` through one shared cursor, round and
    round.  The decode programs the ramp cannot reach are reached by the
    mix's ``warm`` requests.
    """

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.clients = n = int(mix["clients"])
        pairs = multiset(mix)
        if len(pairs) < n:
            raise ValueError(
                f"mix has {len(pairs)} requests for {n} clients")
        perm = rng_for(seed, 1).permutation(len(pairs))
        self.order = [pairs[int(i)] for i in perm]
        shortest = int(mix["new_tokens"][0])
        self.ramp = [(p, max(1, math.ceil(shortest * (i + 1) / n)))
                     for i, (p, _) in enumerate(self.order[:n])]
        self._cursor = n
        self._lock = threading.Lock()

    def warm(self) -> list:
        return [(int(p), int(n)) for p, n in self.mix.get("warm", [])]

    def next_request(self):
        """(index, (prompt length, new tokens)) of the next request
        after the ramp; the index names its token ids."""
        with self._lock:
            idx = self._cursor
            self._cursor += 1
        return idx, self.order[idx % len(self.order)]

    def prompt(self, index: int, length: int):
        """Token ids of request ``index`` (ramp: the client's number;
        warm requests: -1, -2, ...), drawn from the seed."""
        stream = (1, index) if index >= 0 else (0, -index)
        return rng_for(self.seed, 2, *stream).integers(
            0, self.vocab, size=int(length), dtype=np.int32)


def train_data(mix: dict, seed: int, img: int, classes: int):
    """The images and 1-based labels a training cell holds on the host:
    ``host_batches`` global batches, every row different, N(0, 1) pixels.
    Drawn a batch a stream, on a few threads (numpy draws without the
    interpreter lock), because every run pays for it in set-up."""
    from concurrent.futures import ThreadPoolExecutor

    batch, n_batches = int(mix["batch"]), int(mix["host_batches"])
    x = np.empty((batch * n_batches, 3, img, img), np.float32)

    def fill(b):
        rng_for(seed, 3, b).standard_normal(
            out=x[b * batch:(b + 1) * batch], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(n_batches)))
    y = (rng_for(seed, 4).integers(0, classes, size=len(x)) + 1
         ).astype(np.float32)
    return x, y
