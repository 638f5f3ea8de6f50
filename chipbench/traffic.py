"""The one general traffic generator. A traffic mix is a data file
(``chipbench/traffic/<name>.json``) naming a ``kind`` and its parameters;
a later PR adds a mix by adding a file, never code.

Kinds:

``packed``       training batches: ``batch_per_chip`` rows of ``seq_len``
                 seeded token ids, a pool of ``pool`` distinct batches
                 cycled back to back.
``open-loop``    requests due at times fixed by the file: ``rate_per_s``
                 arrivals a second, Poisson gaps drawn from the file's own
                 ``schedule_seed`` and rescaled to fill the window exactly,
                 preceded by ``preroll_s`` seconds of the same traffic.
``closed-loop``  ``clients`` callers, each sending its next request when
                 the last one ended, started ``stagger_s`` apart.

Requests (both serving kinds): a prompt is one of ``shared_prompts.count``
shared prefixes of ``shared_prompts.tokens`` tokens (popularity Zipf with
exponent ``zipf``) followed by a unique part; unique-part and output
lengths are log-normal (``median``, ``sigma``) clipped to ``[lo, hi]``.

**What the file fixes and what the seed fixes.** Lengths are not drawn:
they are the distribution's quantiles at equally spaced probabilities,
dealt into blocks of 8 consecutive requests so that each block holds one
length from each octile (which quantile of the octile goes to which block,
how prompt and output octiles pair, and the order inside a block are
permutations from ``schedule_seed``). So the due times, the number of
requests and every request's (prompt, output) lengths are a pure function
of the file and ``--seconds``: every seed offers the same work at the same
times. ``--seed`` decides which shared prefix a request carries and every
token id (and, in the cell's driver, the weights). The order inside a block
was the seed's at first; three seeds then read ``ttft_mean_ms`` 738, 788
and 859 ms on the chip (PR 24), which no bound of 10% can hold, because a
long prompt ahead of a cluster of arrivals delays them all through the one
prefill lane.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

BLOCK = 8


@dataclass
class Slot:
    """One request as the traffic file fixes it."""
    index: int          # position in the stream (pre-roll first)
    due_s: float        # seconds from the window's opening (<0: pre-roll)
    unique_len: int
    out_len: int


@dataclass
class Request:
    slot: Slot
    prompt: List[int]
    max_new_tokens: int

    @property
    def due_s(self) -> float:
        return self.slot.due_s


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` quantiles of the clipped log-normal at equally spaced
    probabilities, ascending."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    q = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), spec["lo"], spec["hi"]).astype(int)


def _deal(spec: dict, n_blocks: int, rng: np.random.RandomState):
    """[n_blocks, 8]: row b holds one quantile from each octile."""
    q = _quantiles(spec, n_blocks * BLOCK).reshape(BLOCK, n_blocks)
    return np.stack([q[j][rng.permutation(n_blocks)]
                     for j in range(BLOCK)], axis=1)


def _lengths(traffic: dict, n: int) -> np.ndarray:
    """[n, 2] (unique_len, out_len), fixed by the file alone."""
    rng = np.random.RandomState(traffic["schedule_seed"])
    n_blocks = -(-n // BLOCK)
    uniq = _deal(traffic["unique_tokens"], n_blocks, rng)
    out = _deal(traffic["output_tokens"], n_blocks, rng)
    pairs = np.empty((n_blocks, BLOCK, 2), int)
    for b in range(n_blocks):
        # pair prompt octiles with output octiles by a file permutation,
        # so a long prompt is not always a long answer, and order the
        # block by another
        order = rng.permutation(BLOCK)
        pairs[b, :, 0] = uniq[b][order]
        pairs[b, :, 1] = out[b][rng.permutation(BLOCK)]
    return pairs.reshape(n_blocks * BLOCK, 2)[:n]


def _poisson_times(n: int, span: float, rng) -> np.ndarray:
    """``n`` arrival times in [0, span): exponential gaps rescaled so
    that they fill the span exactly."""
    if n == 0:
        return np.zeros((0,))
    gaps = rng.exponential(1.0, n + 1)
    t = np.cumsum(gaps)[:n] - gaps[0] / 2.0
    return t * (span / (np.sum(gaps) - gaps[0] / 2.0 - gaps[-1] / 2.0))


def schedule(traffic: dict, seconds: float) -> List[Slot]:
    """The stream of requests as the file fixes it: pre-roll slots (due
    before 0) and then the window's. For ``closed-loop`` the due times
    are only the staggered starts of the first ``clients`` requests; the
    stream continues for as long as the callers keep asking
    (``more_slots``)."""
    kind = traffic["kind"]
    if kind == "open-loop":
        rate = traffic["rate_per_s"]
        pre_s = traffic["preroll_s"]
        n_pre, n_win = int(round(rate * pre_s)), int(round(rate * seconds))
        rng = np.random.RandomState(traffic["schedule_seed"] + 1)
        due = np.concatenate([_poisson_times(n_pre, pre_s, rng) - pre_s,
                              _poisson_times(n_win, seconds, rng)])
    elif kind == "closed-loop":
        n = traffic["clients"]
        first = more_slots(traffic, 0, n)
        for i, slot in enumerate(first):
            slot.due_s = -traffic["stagger_s"] * (1.0 - i / n)
        return first
    else:
        raise ValueError(f"traffic kind {kind!r} has no request schedule")
    lens = _lengths(traffic, len(due))
    return [Slot(i, float(due[i]), int(lens[i, 0]), int(lens[i, 1]))
            for i in range(len(due))]


def more_slots(traffic: dict, start: int, count: int) -> List[Slot]:
    """Slots ``start .. start+count`` of a closed-loop stream (due at
    once: a caller sends when its last request has ended). Lengths come
    from cycles of ``cycle`` quantiles, so any index is defined."""
    cycle = traffic.get("cycle", 64)
    lens = _lengths(traffic, cycle)
    return [Slot(i, 0.0, int(lens[i % cycle, 0]), int(lens[i % cycle, 1]))
            for i in range(start, start + count)]


class Filler:
    """Turns slots into requests with the seed: the shared prefix and the
    token ids."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic, self.vocab, self.seed = traffic, vocab, int(seed)
        sp = traffic["shared_prompts"]
        rng = self._rng(0xC0FFEE)
        self.shared = rng.randint(0, vocab, (sp["count"], sp["tokens"]))
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp.get("zipf", 1.0)
        self.popularity = w / w.sum()

    def _rng(self, salt: int) -> np.random.RandomState:
        return np.random.RandomState(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, salt & 0xFFFFFFFF])

    def fill(self, slots: List[Slot]) -> List[Request]:
        out: List[Request] = []
        for slot in slots:
            rng = self._rng(slot.index + 1)
            which = rng.choice(len(self.popularity), p=self.popularity)
            unique = rng.randint(0, self.vocab, slot.unique_len)
            out.append(Request(
                slot, [int(t) for t in self.shared[which]]
                + [int(t) for t in unique], slot.out_len))
        return out


class Stream:
    """A closed loop's requests after the callers' first ones, in order,
    made a block at a time as the callers ask for them."""

    def __init__(self, traffic: dict, filler: Filler, start: int):
        if start % BLOCK:
            raise ValueError("closed-loop clients must be a multiple of 8")
        self.traffic, self.filler = traffic, filler
        self.ready: List[Request] = []
        self.next_index = start

    def take(self) -> Request:
        if not self.ready:
            self.ready = self.filler.fill(
                more_slots(self.traffic, self.next_index, BLOCK))
            self.next_index += BLOCK
        return self.ready.pop(0)


def max_request_tokens(traffic: dict) -> int:
    """The longest prompt plus answer the mix can produce."""
    return (traffic["shared_prompts"]["tokens"]
            + traffic["unique_tokens"]["hi"] + traffic["output_tokens"]["hi"])


def packed_batches(traffic: dict, vocab: int, seed: int, rows: int):
    """``pool`` distinct (tokens, targets) batches of ``rows`` x
    ``seq_len`` ids as numpy int32; targets are the next token (the last
    position wraps to the row's first)."""
    rng = np.random.RandomState(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xBA7C4])
    out = []
    for _ in range(traffic["pool"]):
        tok = rng.randint(0, vocab, (rows, traffic["seq_len"])).astype(
            np.int32)
        out.append((tok, np.roll(tok, -1, axis=1)))
    return out
