"""The one general traffic generator: a mix file's parameters and a seed in,
requests or batches out. The program under test sees only what comes out.

Every seed draws from the SAME set of sizes in another order: a length is
dealt from a shuffled deck of `levels` values spaced geometrically
(log-uniform) between `lo` and `hi`, and the deck is reshuffled when it runs
out. So two seeds do the same work, a run's cost does not swing with the
luck of the draw, and set-up can warm every prompt length a window will use
(the engine compiles small programs per prompt length).
"""

import dataclasses

import numpy as np


def seeds(seed, n):
    """n independent 31-bit seeds from one `--seed` of any size."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def levels(spec):
    """The deck's values: `levels` integers from lo to hi, geometric."""
    k = spec["levels"]
    if k == 1:
        return [int(spec["lo"])]
    return [int(round(v)) for v in np.geomspace(spec["lo"], spec["hi"], k)]


class Deck:
    """Deals `values` in shuffled order, reshuffling when it runs out."""

    def __init__(self, values, rng):
        self.values, self.rng, self.hand = list(values), rng, []

    def deal(self):
        if not self.hand:
            self.hand = list(self.rng.permutation(self.values))
        return self.hand.pop()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray      # int32 token ids in [1, vocab)
    max_new_tokens: int
    temperature: float


class RequestStream:
    """An endless seeded stream of requests for a serving mix
    (`prompt_len`, `answer_len`, `max_total`, `sampled_every`,
    `temperature`). `first_wave(n)` gives n requests whose answers are cut to
    the fractions (k + 0.5) / n of their length, shuffled: the residual
    lengths of a system already in steady state, so a window that opens
    right after they are admitted sees retirements spread evenly."""

    def __init__(self, mix, vocab_size, seed):
        self.mix, self.vocab = mix, vocab_size
        self.rng = np.random.default_rng(seed)
        self.prompts = Deck(levels(mix["prompt_len"]), self.rng)
        self.answers = Deck(levels(mix["answer_len"]), self.rng)
        self.count = 0

    def next(self, fraction=1.0):
        n = self.prompts.deal()
        want = min(self.answers.deal(), self.mix["max_total"] - n)
        want = max(1, int(round(want * fraction)))
        every = self.mix["sampled_every"]
        sampled = every and self.count % every == every - 1
        req = Request(self.rng.integers(1, self.vocab, n).astype(np.int32),
                      want, self.mix["temperature"] if sampled else 0.0)
        self.count += 1
        return req

    def first_wave(self, n):
        fractions = (self.rng.permutation(n) + 0.5) / n
        return [self.next(f) for f in fractions]


def train_batches(mix, vocab_size, seed):
    """(ids, labels), each int32 [distinct_batches, batch, seq]: the token
    batches a training window cycles through."""
    rng = np.random.default_rng(seed)
    shape = (mix["distinct_batches"], mix["batch"], mix["seq"])
    return (rng.integers(0, vocab_size, shape).astype(np.int32),
            rng.integers(0, vocab_size, shape).astype(np.int32))
