"""The seeded traffic generator: same seed, same schedule; only warmed
prompt lengths; open-loop due times fixed before the run."""
import itertools
import json
import os

import numpy as np

from harness import traffic as tr

CELLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "cells")


def _cell(name):
    with open(os.path.join(CELLS, name)) as f:
        return json.load(f)


def _take(it, n):
    return list(itertools.islice(it, n))


def _same(a, b):
    return all(
        x.due_s == y.due_s and x.max_new == y.max_new and np.array_equal(x.prompt, y.prompt)
        for x, y in zip(a, b)
    )


def test_same_seed_same_schedule_open_and_closed():
    for name in ("qwen3-8b.chat.json", "qwen3-8b.offline.json"):
        cell = _cell(name)
        big = 2**31 + 12345
        a = _take(tr.lm_requests(cell, big, 151936), 300)
        b = _take(tr.lm_requests(cell, big, 151936), 300)
        c = _take(tr.lm_requests(cell, big + 1, 151936), 300)
        assert _same(a, b)
        assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_prompt_lengths_only_from_the_warmed_buckets():
    cell = _cell("qwen3-8b.chat.json")
    warmed = set(tr.prompt_lengths(cell))
    assert warmed == {128, 256, 512, 1024}
    reqs = _take(tr.lm_requests(cell, 7, 151936), 500)
    assert {len(r.prompt) for r in reqs} <= warmed
    assert {r.max_new for r in reqs} <= {32, 64, 128}
    assert all(len(r.prompt) + r.max_new <= cell["max_len"] for r in reqs)


def test_every_seed_gets_the_same_work_in_another_order():
    cell = _cell("qwen3-8b.chat.json")
    n = round(cell["rate_per_s"] * cell["period_s"])
    for seed in (1, 2, 3**20):
        period = _take(tr.lm_requests(cell, seed, 151936), n)
        lengths = sorted(len(r.prompt) for r in period)
        outs = sorted(r.max_new for r in period)
        want = np.repeat([128, 256, 512, 1024], tr.exact_counts([0.4, 0.3, 0.2, 0.1], n))
        assert lengths == sorted(want)
        assert outs == sorted(np.repeat([32, 64, 128], tr.exact_counts([0.5, 0.3, 0.2], n)))


def test_open_loop_due_times_are_fixed_before_the_run():
    cell = _cell("qwen3-8b.chat.json")
    n = round(cell["rate_per_s"] * cell["period_s"])
    reqs = _take(tr.lm_requests(cell, 5, 151936), 3 * n)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    # each period's gaps are the exponential's quantiles at the cell's rate
    period_len = due[n] - due[0]
    assert abs(period_len - cell["period_s"]) < 0.1 * cell["period_s"]
    gaps = np.sort(np.diff(due[: n + 1]))
    want = np.sort(-np.log1p(-(np.arange(n) + 0.5) / n) / cell["rate_per_s"])
    np.testing.assert_allclose(gaps, want)


def test_closed_loop_staggers_the_first_wave():
    cell = _cell("qwen3-8b.offline.json")
    reqs = _take(tr.lm_requests(cell, 9, 151936), 40)
    slots, max_new = cell["n_slots"], cell["max_new"]
    assert [r.max_new for r in reqs[:slots]] == [max_new * (i + 1) // slots for i in range(slots)]
    assert all(r.max_new == max_new for r in reqs[slots:])
    assert all(len(r.prompt) == cell["prompt_len"] for r in reqs)


def test_sample_holds_the_required_index():
    s = tr.sample_indices(3, 100, 4, [99])
    assert len(s) == 4 and 99 in s and s == tr.sample_indices(3, 100, 4, [99])
