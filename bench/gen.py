"""Traffic generators, driven by a mix's parameters and the run's seed.

``TokenRows``: fine-tuning batches.  Every row of every step is drawn
apart (its own stream from (seed, step)), tokens Zipf-distributed over
the vocabulary through a permutation drawn from the seed, so the work
per step is the same for every seed and only the ids differ.

``open_loop``: serving requests.  Poisson arrivals at the mix's rate,
prompt and output lengths log-normal around their medians and clipped.
The multiset of gaps and lengths is the same for every seed (drawn from
the mix's own ``shape_seed``); the run's seed orders them and draws the
prompt ids.  Each request is timed from when it was due.
"""
from __future__ import annotations

import numpy as np


class TokenRows:
    def __init__(self, vocab: int, seq_len: int, seed: int,
                 exponent: float, offset: float):
        self.vocab, self.seq_len, self.seed = vocab, seq_len, int(seed)
        ranks = np.arange(vocab, dtype=np.float64)
        w = 1.0 / (ranks + offset) ** exponent
        self._cdf = np.cumsum(w / w.sum())
        self._perm = np.random.default_rng([self.seed, 1]).permutation(vocab)

    def batch_at(self, step: int, batch_size: int):
        rng = np.random.default_rng([self.seed, 2, int(step)])
        u = rng.random((batch_size, self.seq_len + 1))
        ids = self._perm[np.minimum(np.searchsorted(self._cdf, u),
                                    self.vocab - 1)].astype(np.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def _lognormal(rng, n, median, sigma, lo, hi):
    x = np.exp(np.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.rint(x), lo, hi).astype(int)


def open_loop(mix: dict, vocab: int, seed: int, seconds: float):
    """[(due_s, prompt ids, max_new)]: round(rate * seconds) requests
    whose Poisson gaps are scaled to end at the window's close."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    shape = np.random.default_rng(mix["shape_seed"])
    gaps = shape.exponential(1.0, n)
    p_len = _lognormal(shape, n, **mix["prompt_len"])
    o_len = _lognormal(shape, n, **mix["output_len"])
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    due = np.cumsum(gaps[rng.permutation(n)])
    due = (due - due[0]) * (seconds / due[-1]) if n > 1 else np.zeros(1)
    order = rng.permutation(n)
    return [(float(due[i]),
             rng.integers(0, vocab, p_len[order[i]], dtype=np.int32),
             int(o_len[order[i]])) for i in range(n)]


def longest_first(mix: dict) -> list:
    """[(prompt_len, max_new)] covering every prefill chunk shape the mix
    can produce, longest first (for warm-up)."""
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    c = mix["serve_spec"]["prefill_chunk"]
    lens = sorted({lo} | {min(hi, max(lo, c * j + r + 1))
                          for j in (1, 2) for r in range(c)}, reverse=True)
    return [(n, 2) for n in lens]
