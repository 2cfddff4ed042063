"""The benchmark's traffic generator: deterministic per seed, the same
sizes for every seed, and the distributions its mix files state."""

import json
import math
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import Mix, generate, quantile_sizes, warmup_lengths  # noqa: E402


def mix(name):
    return Mix.from_dict(name, json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", ["code", "longdoc"])
def test_same_seed_same_requests(name):
    a = generate(mix(name), 2.0, 30.0, 123, 1000)
    b = generate(mix(name), 2.0, 30.0, 123, 1000)
    assert [(x.due_s, x.n_out, x.prompt.tolist()) for x in a] == [
        (x.due_s, x.n_out, x.prompt.tolist()) for x in b
    ]


@pytest.mark.parametrize("name", ["code", "longdoc"])
def test_every_seed_offers_the_same_schedule(name):
    a = generate(mix(name), 2.0, 30.0, 1, 1000)
    b = generate(mix(name), 2.0, 30.0, 2**40 + 7, 1000)
    assert [(x.due_s, len(x.prompt), x.n_out) for x in a] == [
        (x.due_s, len(x.prompt), x.n_out) for x in b
    ]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_schedule_is_shuffled_not_sorted():
    r = generate(mix("code"), 2.0, 30.0, 1, 1000)
    lengths = [len(x.prompt) for x in r]
    assert lengths != sorted(lengths) and lengths != sorted(lengths, reverse=True)


def test_arrivals_fill_the_window():
    r = generate(mix("code"), 3.0, 20.0, 9, 1000)
    assert len(r) == 60
    due = [x.due_s for x in r]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0
    # Poisson: inter-arrival gaps exponential, coefficient of variation ~1.
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_token_ids_in_range_and_seeded():
    r = generate(mix("code"), 2.0, 10.0, 5, 777)
    ids = np.concatenate([x.prompt for x in r])
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 777
    other = generate(mix("code"), 2.0, 10.0, 6, 777)
    assert not np.array_equal(r[0].prompt[:8], other[0].prompt[: len(r[0].prompt[:8])])


def test_lognormal_quantiles_follow_the_mix():
    d = mix("code").prompt_tokens
    sizes = np.sort(quantile_sizes(d, 2001))
    assert sizes.min() >= d["min"] and sizes.max() == d["max"]  # the upper tail is clipped
    assert abs(statistics.median(sizes) - d["median"]) <= 1
    # Half a sigma either side of the median, both inside the clips.
    hi = sizes[int(round(0.691462 * 2001 - 0.5))]
    lo = sizes[int(round(0.308538 * 2001 - 0.5))]
    assert hi == pytest.approx(d["median"] * math.exp(0.5 * d["sigma"]), abs=3)
    assert lo == pytest.approx(d["median"] * math.exp(-0.5 * d["sigma"]), abs=3)


def test_uniform_quantiles_cover_the_range():
    d = mix("longdoc").output_tokens
    sizes = quantile_sizes(d, 193)
    assert sizes.min() == d["min"] and sizes.max() == d["max"]
    assert abs(sizes.mean() - (d["min"] + d["max"]) / 2) < 1.0


def test_warmup_covers_every_window_length():
    m = mix("code")
    for seed in (1, 2, 3):
        lengths = {len(x.prompt) for x in generate(m, 2.5, 30.0, seed, 1000)}
        assert lengths <= set(warmup_lengths(m, 2.5, 30.0))
