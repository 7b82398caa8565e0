"""The one traffic generator: a mix file's parameters and a seed in,
an open-loop schedule out.

Every seed gets the SAME set of gaps, prompt lengths and output
lengths: the n evenly spaced quantiles of each distribution. The seed
only decides their order (which gap precedes which request, which
prompt gets which answer length) and the prompts' bytes. So two seeds
offer the same work at the same mean rate, and what differs between
runs is the system, not the draw.

The order is a plain shuffle unless the mix file says ``"order":
{"strata": k}``: then each list is dealt in rounds of k, one value from
every k-th part of its sorted grid a round. A window that is not
drained needs it: under a plain shuffle one seed puts its long answers
last, where the close cuts them, and completes 3% fewer tokens than
the next seed on the same system.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List

_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def quantile_grid(spec: Dict[str, Any], n: int) -> List[float]:
    """n values at the quantiles (i + 0.5) / n of ``spec``:
    {"dist": "lognormal", "median", "sigma"} or {"dist": "constant",
    "value"}, then clipped to [min, max] where the spec has them."""
    if spec["dist"] == "constant":
        values = [float(spec["value"])] * n
    elif spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        inv = NormalDist().inv_cdf
        values = [math.exp(mu + sigma * inv((i + 0.5) / n))
                  for i in range(n)]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo = spec.get("min", -math.inf)
    hi = spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in values]


def _in_rounds(values: List[float], rng: random.Random,
               strata: int) -> List[float]:
    """``values`` in the seed's order. One stratum: a plain shuffle.
    k strata: the sorted values are cut into k slices of equal size
    and dealt in rounds, each round one value of every slice in a
    shuffled order, so any k consecutive requests carry about the same
    work whatever the seed."""
    if strata <= 1:
        values = list(values)
        rng.shuffle(values)
        return values
    values = sorted(values)
    n = len(values)
    slices = [values[n * i // strata:n * (i + 1) // strata]
              for i in range(strata)]
    for s in slices:
        rng.shuffle(s)
    out = []
    for j in range(max(len(s) for s in slices)):
        round_ = [s[j] for s in slices if j < len(s)]
        rng.shuffle(round_)
        out += round_
    return out


def open_loop_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                       scale: Dict[str, float] = None
                       ) -> List[Dict[str, Any]]:
    """Requests of one window: ``due`` (seconds from the window's
    start), ``prompt`` (ASCII, one byte a token), ``max_tokens``.

    round(rate x seconds) requests; the gaps are scaled so that the
    last request is due half a mean gap before the window closes, so
    the mean rate over the window is the file's. A mix with
    ``initial_burst`` sends that many more at the start, 10 ms apart:
    a saturated cell fills every slot in the first second and keeps
    them full with arrivals at the rate the system completes.
    ``scale`` shrinks lengths for the CPU rehearsal ({"prompt": 0.1,
    "output": 0.1}).
    """
    scale = scale or {}
    burst = int(mix.get("initial_burst", 0))
    steady = max(1, round(mix["rate_rps"] * seconds))
    n = burst + steady
    rng = random.Random(seed)
    gaps = quantile_grid(mix["gap"], steady)
    total = sum(gaps)
    # the last request is due half a mean gap before the close
    gaps = [g * seconds * (steady - 0.5) / steady / total for g in gaps]
    prompts = [max(1, round(v * scale.get("prompt", 1.0)))
               for v in quantile_grid(mix["prompt_bytes"], n)]
    outputs = [max(1, round(v * scale.get("output", 1.0)))
               for v in quantile_grid(mix["output_tokens"], n)]
    strata = int(mix.get("order", {}).get("strata", 1))
    gaps, prompts, outputs = (_in_rounds(values, rng, strata)
                              for values in (gaps, prompts, outputs))
    dues = [0.01 * (i + 1) for i in range(burst)]
    due = 0.0
    for gap in gaps:
        due += gap
        dues.append(due)
    dues.sort()
    requests = []
    for due, n_bytes, n_out in zip(dues, prompts, outputs):
        requests.append({
            "due": due,
            "prompt": "".join(rng.choices(_LETTERS, k=n_bytes)),
            "max_tokens": n_out})
    return requests


def token_rows(seed: int, seq: int, vocab: int):
    """map_batches fn of a training job: row id -> (tokens, targets),
    random tokens that depend on the seed and the row alone and never
    repeat, so the feed does its work every step."""
    def tokenize(rows):
        import numpy as np
        seqs = np.stack([
            np.random.default_rng([seed, int(i)]).integers(
                0, vocab, seq + 1) for i in rows["id"]])
        return {"tokens": seqs[:, :-1].astype(np.int32),
                "targets": seqs[:, 1:].astype(np.int32)}
    return tokenize
