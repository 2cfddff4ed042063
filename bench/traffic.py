"""The one traffic generator: a mix's data file and a seed in, requests out.

A mix (``bench/traffic/<name>.json``) states distributions of prompt and
output lengths, the arrival process, the energy harvest per slot and any
fleet events. A cell (``bench/cells/<name>.json``) states the offered rate.

Every seed gets the same schedule: the sizes are the distribution's
quantiles at ``(i + 0.5) / n``, and one fixed draw (``SCHEDULE``), not the
run's seed, orders them, pairs prompt with output lengths and orders the
inter-arrival gaps (the quantiles of an exponential). The run's seed draws
the token ids. A window holds tens of requests at the rates the cells run,
so an order drawn from the seed would change the work inside the window:
with the order drawn from the seed, two runs of one seed agreed within
0.2% and six seeds spread by 16-24% (``PERF.md``). The arrivals are
stretched so that the n-th request falls due inside the window.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

__all__ = ["Mix", "Arrival", "generate", "quantile_sizes", "rng_for"]

_NORMAL = statistics.NormalDist()
SCHEDULE = 0  # the stream that orders every window's sizes and gaps


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float  # offset from the window's start
    prompt: np.ndarray  # int32 token ids
    n_out: int  # tokens to generate


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    prompt_tokens: dict
    output_tokens: dict
    arrivals: str = "poisson"
    energy: dict = dataclasses.field(default_factory=dict)
    events: tuple = ()

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        return cls(
            name=name,
            prompt_tokens=dict(d["prompt_tokens"]),
            output_tokens=dict(d["output_tokens"]),
            arrivals=d.get("arrivals", "poisson"),
            energy=dict(d.get("energy", {})),
            events=tuple(d.get("events", ())),
        )


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    return np.random.default_rng([int(stream), int(seed) & (2**64 - 1)])


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"] + 1)
    if kind == "exponential":
        return -dist["mean"] * math.log(1.0 - u)
    raise ValueError(f"unknown distribution {kind!r}")


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` whole sizes at the quantiles ``(i + 0.5) / n``, clipped to
    ``[min, max]``, in ascending order."""
    lo, hi = int(dist["min"]), int(dist["max"])
    vals = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def n_requests(rate_per_s: float, seconds: float) -> int:
    return max(1, int(round(rate_per_s * seconds)))


def generate(mix: Mix, rate_per_s: float, seconds: float, seed: int, vocab: int):
    """The window's requests, in due order."""
    n = n_requests(rate_per_s, seconds)
    order = rng_for(SCHEDULE, 1)
    prompts = order.permutation(quantile_sizes(mix.prompt_tokens, n))
    outputs = order.permutation(quantile_sizes(mix.output_tokens, n))
    if mix.arrivals != "poisson":
        raise ValueError(f"unknown arrival process {mix.arrivals!r}")
    gaps = order.permutation(
        [_quantile({"dist": "exponential", "mean": 1.0}, (i + 0.5) / n) for i in range(n)]
    )
    # Due times start at 0 and the last one falls one gap short of the
    # window's end: every request of the mix is due inside the window.
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / float(np.sum(gaps)))
    ids = rng_for(seed, 2)
    return [
        Arrival(float(t), ids.integers(0, vocab, size=int(p)).astype(np.int32), int(o))
        for t, p, o in zip(due, prompts, outputs)
    ]


def warmup_lengths(mix: Mix, rate_per_s: float, seconds: float) -> list[int]:
    """Every distinct prompt length the window will send, whatever the seed."""
    n = n_requests(rate_per_s, seconds)
    return sorted({int(x) for x in quantile_sizes(mix.prompt_tokens, n)})
