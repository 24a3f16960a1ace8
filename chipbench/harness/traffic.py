"""One general generator for every traffic mix, read from its cell file.

Every seed gets the same work in another order: the multiset of prompt
lengths, output lengths and inter-arrival gaps is fixed by the cell
file, and the seed only shuffles it and draws the token ids. So two
seeds differ by arrangement, never by the amount of work. Open-loop due
times are fixed before the run starts; they never depend on how fast
the system runs.

Mixes (``"mode"`` in the cell file):

* ``closed`` LM: ``n_slots`` requests always in flight, each
  ``prompt_len`` tokens in and ``max_new`` out, a finished one replaced
  at once. The first wave's outputs are cut to
  ``max_new * (i + 1) / n_slots`` so that completions, and the
  admissions that replace them, are spread evenly instead of arriving
  all at once.
* ``open`` LM: arrivals at ``rate_per_s``, with exponential gaps
  (their quantiles, shuffled), prompt and output lengths drawn from
  weighted buckets in exact proportion over each ``period_s``.
* ``closed`` CNN: one client, ``batch`` images a call, the next call
  once the last call's logits are on the host.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class LmRequest:
    index: int
    due_s: float  # offset from the start of the schedule (open loop); 0 for closed
    prompt: np.ndarray
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *map(int, stream)]))


def exact_counts(weights: list[float], n: int) -> list[int]:
    """Split ``n`` by ``weights`` exactly (largest remainders)."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    base = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - base), kind="stable")[: n - base.sum()]:
        base[i] += 1
    return base.tolist()


def bucket_draws(buckets: list[list], n: int, rng: np.random.Generator) -> np.ndarray:
    sizes = [int(b[0]) for b in buckets]
    counts = exact_counts([float(b[1]) for b in buckets], n)
    out = np.repeat(sizes, counts)
    rng.shuffle(out)
    return out


def exponential_gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps at the exponential's quantiles ``(i + 1/2) / n``, in a
    seeded order: Poisson arrivals with no draw in how many there are."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    return gaps


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    return rng_for(seed, 1, index).integers(0, vocab, length, dtype=np.int32)


def lm_closed(cell: dict, seed: int, vocab: int) -> Iterator[LmRequest]:
    n_slots, max_new = int(cell["n_slots"]), int(cell["max_new"])
    i = 0
    while True:
        out = max_new
        if i < n_slots:
            out = max(1, math.ceil(max_new * (i + 1) / n_slots))
        yield LmRequest(i, 0.0, prompt_tokens(seed, i, int(cell["prompt_len"]), vocab), out)
        i += 1


def lm_open(cell: dict, seed: int, vocab: int) -> Iterator[LmRequest]:
    """Arrivals period after period; each period holds
    ``round(rate * period_s)`` requests whose gaps sum to about
    ``period_s``."""
    rate, period = float(cell["rate_per_s"]), float(cell["period_s"])
    n = max(1, round(rate * period))
    t, i, p = 0.0, 0, 0
    while True:
        rng = rng_for(seed, 2, p)
        gaps = exponential_gaps(n, rate, rng)
        plens = bucket_draws(cell["prompt_buckets"], n, rng)
        outs = bucket_draws(cell["output_buckets"], n, rng)
        for g, plen, out in zip(gaps, plens, outs):
            yield LmRequest(i, t, prompt_tokens(seed, i, int(plen), vocab), int(out))
            t += float(g)
            i += 1
        p += 1


def lm_requests(cell: dict, seed: int, vocab: int) -> Iterator[LmRequest]:
    if cell["mode"] == "closed":
        return lm_closed(cell, seed, vocab)
    if cell["mode"] == "open":
        return lm_open(cell, seed, vocab)
    raise ValueError(f"unknown LM traffic mode {cell['mode']!r}")


def prompt_lengths(cell: dict) -> list[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    if cell["mode"] == "closed":
        return [int(cell["prompt_len"])]
    return sorted({int(b[0]) for b in cell["prompt_buckets"]})


def sample_indices(seed: int, n: int, k: int, must: list[int] = ()) -> list[int]:
    """``k`` of ``range(n)`` drawn from the seed, always holding ``must``."""
    rng = rng_for(seed, 3)
    rest = [i for i in rng.permutation(n).tolist() if i not in must]
    return sorted(list(must)[:k] + rest[: max(0, k - len(must))])
