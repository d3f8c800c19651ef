"""The one general traffic generator: a mix file's parameters and a seed in,
requests or batches out. The program under test sees only what comes out.

Every seed draws from the SAME set of sizes in another order: a length is
dealt from a shuffled deck of `levels` values spaced geometrically
(log-uniform) between `lo` and `hi`, and the deck is reshuffled when it runs
out. So two seeds do the same work, a run's cost does not swing with the
luck of the draw, and set-up can warm every prompt length a window will use
(the engine compiles small programs per prompt length).

A mix with `"balanced": true` fixes more of the work, for cells whose
requests live longer than a window and whose windows admit a few dozen: it
deals (prompt, answer) PAIRS, each round of `pair_order` holding every pair
of levels once, and its first wave is the same requests for every seed, sent
in another order. Without it the two lengths come from decks of their own,
so which prompts go with which answers, and the residual answers of the
wave, change with the seed, and so does the work a window can do.
"""

import dataclasses
import math

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


def pair_order(n_prompts, n_answers):
    """Every (prompt level, answer level) index pair once, interleaved so
    that each n_prompts consecutive pairs hold every prompt level once and
    each n_answers consecutive pairs every answer level once (for equal
    counts, the rows of a Latin square)."""
    lcm = math.lcm(n_prompts, n_answers)
    return [(k % n_prompts, (k + k // lcm) % n_answers)
            for k in range(n_prompts * n_answers)]


class PairDeck:
    """Deals (prompt, answer) lengths in rounds of `pair_order`, the levels
    of each round relabelled by the seed: every round holds every pair."""

    def __init__(self, prompts, answers, rng):
        self.prompts, self.answers, self.rng = prompts, answers, rng
        self.order = pair_order(len(prompts), len(answers))
        self.hand = []

    def deal(self):
        if not self.hand:
            sp = self.rng.permutation(len(self.prompts))
            sa = self.rng.permutation(len(self.answers))
            self.hand = [(self.prompts[sp[p]], self.answers[sa[a]])
                         for p, a in reversed(self.order)]
        return self.hand.pop()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray      # int32 token ids in [1, vocab)
    max_new_tokens: int
    temperature: float


class RequestStream:
    """An endless seeded stream of requests for a serving mix
    (`prompt_len`, `answer_len`, `max_total`, `sampled_every`,
    `temperature`, optionally `balanced`). `first_wave(n)` gives n requests
    whose answers are cut to the fractions (k + 0.5) / n of their length,
    shuffled: the residual lengths of a system already in steady state, so a
    window that opens right after they are admitted sees retirements spread
    evenly."""

    def __init__(self, mix, vocab_size, seed):
        self.mix, self.vocab = mix, vocab_size
        self.rng = np.random.default_rng(seed)
        self.prompt_levels = levels(mix["prompt_len"])
        self.answer_levels = levels(mix["answer_len"])
        if mix.get("balanced"):
            pairs = PairDeck(self.prompt_levels, self.answer_levels, self.rng)
            self.deal = pairs.deal
        else:
            prompts = Deck(self.prompt_levels, self.rng)
            answers = Deck(self.answer_levels, self.rng)
            self.deal = lambda: (prompts.deal(), answers.deal())
        self.count = 0

    def next(self, fraction=1.0, lengths=None):
        n, answer = lengths or self.deal()
        want = min(answer, self.mix["max_total"] - n)
        want = max(1, int(round(want * fraction)))
        every = self.mix["sampled_every"]
        sampled = every and self.count % every == every - 1
        req = Request(self.rng.integers(1, self.vocab, n).astype(np.int32),
                      want, self.mix["temperature"] if sampled else 0.0)
        self.count += 1
        return req

    def first_wave(self, n):
        if not self.mix.get("balanced"):
            fractions = (self.rng.permutation(n) + 0.5) / n
            return [self.next(f) for f in fractions]
        # the same n (prompt, answer, fraction) for every seed: `pair_order`
        # over and over, the k-th cut to (k + 0.5) / n; the seed sends them
        # in another order
        order = pair_order(len(self.prompt_levels), len(self.answer_levels))
        wave = [(order[k % len(order)], (k + 0.5) / n) for k in range(n)]
        return [self.next(f, (self.prompt_levels[p], self.answer_levels[a]))
                for (p, a), f in (wave[i] for i in self.rng.permutation(n))]


def train_batches(mix, vocab_size, seed):
    """(ids, labels), each int32 [distinct_batches, batch, seq]: the token
    batches a training window cycles through."""
    rng = np.random.default_rng(seed)
    shape = (mix["distinct_batches"], mix["batch"], mix["seq"])
    return (rng.integers(0, vocab_size, shape).astype(np.int32),
            rng.integers(0, vocab_size, shape).astype(np.int32))
