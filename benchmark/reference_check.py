"""The comparison that decides ``correct`` for the arithmetic: the
program against the configuration's plain reference, on the chip, at
the cell's real sizes, outside the timed window.

Serving: the engine the replica will build (same EngineConfig, same
seed, so the same weights) generates a few tokens for a few prompts
through its normal path (bucketed prefill into a cache slot, then
whole-batch decode steps) and records the log-probability of every
token it chose. The reference scores the same prompt-plus-output
sequences in one full forward pass; the two log-probabilities must
agree. Log-probabilities and not tokens, because with random weights
the largest logit changes on rounding.

Training: the program's loss on sequences of the first batch against
the reference's loss on the same weights.

TOLERANCE: the program computes in bf16 (8 bits of mantissa, 2^-8
relative steps) through up to 16 layers where the reference computes
in float32, so log-probabilities over a 32k vocabulary honestly differ
by a few hundredths. On the chip, mistral-7b-v0.3-L16 read a worst
difference of 0.022 to 0.039 and a mean of 0.009 to 0.013 (six runs,
PR 24); the limits are about four times that. A wrong mask, rotary
phase or a dropped layer moves them by tenths to units. The mean loss
of a batch averages the per-token differences out: at 4 layers and
8192 tokens the two losses differed by 7e-6; the limit is 1e-3.

ROUTED: a model with a router (``routed``, programs/) cannot be held to
the worst token. The reference chooses each token's experts from its
float32 hidden states, the program from its bf16 ones; where a router's
k-th and (k+1)-th logits lie closer than that rounding the two choose
different experts and that token's log-probability moves by tenths to
units, in a program that is right (in float32 the same program agrees
to 1e-4 on every token: benchmark/tests). A flip also reaches later
positions through attention. So the routed comparison scores 192
tokens and not 18, calls a token DECIDED when the reference's router
margin at its position (k-th less (k+1)-th logit, the smallest over the
layers) is at least ROUTER_MARGIN, and gates on statistics that a
handful of flips cannot move and a fault in the layer must. ``worst``
is reported and decides nothing.

Each limit is at least twice the widest reading of a program that drops
nothing and at most half the lowest reading of one that drops, both the
program's own expert layer at Mixtral-8x7B-v0.1 widths (4 layers, 8
experts of 14336, top-2, bf16, max_batch 32), through this check, on
the chip (PR 28): the stand-in with moe_capacity_factor=4.0 (an
expert's capacity is every row of the step, so nothing overflows),
18 seeds, and the control with the default 2.0 (which counts padding
and idle rows and drops real tokens), 12 seeds. Readings as stand-in /
control, then the limit:

  decided share at margin 0.04   0.62-0.79 / 0.61-0.76; floor 0.3
  mean of decided tokens         0.0090-0.0157 / 0.118-0.336; the
                                 dense LOGPROB_MEAN_TOL, 0.04
  share of decided tokens over   0 of 119-152 on every seed /
    LOGPROB_TOL                  0.203-0.582; 0.05
  median of decided tokens       0.0065-0.0119 / 0.056-0.197; 0.026
  median of all tokens           0.0076-0.0142 / 0.057-0.197: twice the
                                 one is 0.0284 and half the other
                                 0.0286, no room: reported, no gate
  mean of all tokens             0.012-0.049 / 0.136-0.346: twice the
                                 one passes half the other: no gate
  worst                          0.21-1.80 / 0.93-1.79: no gate

The precision under bf16: the stand-in with its expert weights rounded
to fp8 (4 exponent bits, 3 of mantissa, a scale for each output
channel) read a decided mean of 0.053-0.069 and a decided median of
0.035-0.046 on 4 seeds and failed on each, 3.4 and 2.9 times the
stand-in's widest. Rounded to int8 with such a scale (127 even steps)
it read 0.023-0.024 and 0.018-0.022 on 3 seeds and PASSED: 1.5 times
the stand-in's widest, which no limit with room over a sound seed can
catch. An int8 expert path needs a sharper statistic first.

ROUTER_MARGIN is the smallest of 0.02, 0.03, ... at which no decided
token of the stand-in read over LOGPROB_TOL on any seed; at 0.03 the
decided mean (0.0094-0.0166) already had its room and one token of 147
read over on one seed, at 0.02 the mean did not (0.024). The decided
share has a floor so that a margin cannot be raised until nothing is
left to judge.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, List, Optional

from benchmark.harness import BenchError

LOGPROB_TOL = 0.15        # worst |difference| of a chosen token's logprob
LOGPROB_MEAN_TOL = 0.04   # mean |difference|
LOSS_TOL = 1e-3           # |program loss - reference loss|, nats
# a model with a router: see ROUTED above for the readings
ROUTER_MARGIN = 0.04             # router logits; under it a token is undecided
ROUTED_DECIDED_SHARE_MIN = 0.3   # decided tokens, of all
ROUTED_OVER_SHARE_MAX = 0.05     # decided tokens over LOGPROB_TOL, of decided
ROUTED_MEDIAN_MAX = 0.026        # median |difference| of decided tokens

# The constants above are what a configuration is judged by where its
# file says nothing. A file's ``"check": {"limits": {...}}`` overrides
# any of them for that configuration (check_limits): a family whose
# logits are scaled, or whose router has many small experts, brings its
# own limits, margin and floor, calibrated by the rule above through
# ``benchmark/run.py --control`` (programs/__init__.py), and writes the
# readings beside them under ``"check": {"calibration": ...}``.
DENSE_LIMITS = {"worst": LOGPROB_TOL, "mean": LOGPROB_MEAN_TOL}
ROUTED_LIMITS = {
    "router_margin": ROUTER_MARGIN,
    "decided_share_at_least": ROUTED_DECIDED_SHARE_MIN,
    "decided_mean": LOGPROB_MEAN_TOL,
    "decided_median": ROUTED_MEDIAN_MAX,
    "decided_over_share": ROUTED_OVER_SHARE_MAX,
    "over": LOGPROB_TOL}     # the level a decided token is counted over
# the statistics a report gates on, and the limit each is held to
GATES = {"worst": "worst", "mean": "mean",
         "decided_share": "decided_share_at_least",
         "decided_mean": "decided_mean", "decided_median": "decided_median",
         "decided_over_share": "decided_over_share"}
# where --control reads the routed report beside the gating one
CONTROL_MARGINS = (0.005, 0.01, 0.02, 0.04)


# the check's sizes where a configuration file has no "check" key:
# prompts for prefill buckets 128, 256 and 512, all on the flash kernel
CHECK_PROMPTS = (100, 200, 300)
CHECK_TOKENS = 6
# a model with a router is judged by shares and means over its tokens
# (routed_report): enough tokens that a share is one
ROUTED_CHECK_TOKENS = 64


def check_sizes(config: Dict[str, Any], routed: bool):
    """-> (prompt lengths, tokens decoded after each) of the serving
    check: the configuration file's ``"check": {"prompt_lens": [...],
    "new_tokens": n}``, either key or both, else the defaults."""
    check = config.get("check", {})
    return (list(check.get("prompt_lens", CHECK_PROMPTS)),
            int(check.get("new_tokens",
                          ROUTED_CHECK_TOKENS if routed else CHECK_TOKENS)))


def check_limits(config: Dict[str, Any], routed: bool) -> Dict[str, float]:
    """The limits of the serving comparison: the constants, under the
    configuration file's ``"check": {"limits": {...}}``. A key that the
    report of that kind does not gate on is refused."""
    defaults = ROUTED_LIMITS if routed else DENSE_LIMITS
    given = config.get("check", {}).get("limits", {})
    unknown = sorted(set(given) - set(defaults))
    if unknown:
        raise BenchError(
            f"check.limits has {unknown}; a "
            f"{'routed' if routed else 'dense'} report takes "
            f"{sorted(defaults)}")
    return {**defaults, **{k: float(v) for k, v in given.items()}}


def _reference(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


def generate(engine, prompt_lens: List[int], new_tokens: int, seed: int):
    """``engine`` generates ``new_tokens`` greedy tokens for a prompt of
    each length through its normal path (bucketed prefill into a cache
    slot, then whole-batch decode steps with idle slots). -> for each
    prompt (prompt + output ids, the log-probability the engine gave
    each output token)."""
    import random

    from ray_tpu.llm.engine import GenerationRequest

    rng = random.Random(seed)
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=[256] + [rng.randrange(97, 123) for _ in range(n - 1)],
        max_tokens=new_tokens, temperature=0.0, logprobs=0))
        for n in prompt_lens]
    while engine.has_work():
        engine.step()
    out = []
    for r in requests:
        if r.error or len(r.output_ids) != new_tokens:
            raise RuntimeError(f"engine request failed: {r.error!r}, "
                               f"{len(r.output_ids)} tokens")
        if [e["id"] for e in r.logprob_data] != list(r.output_ids):
            raise RuntimeError("logprobs are not those of the output")
        out.append((list(r.prompt_ids) + list(r.output_ids),
                    [e["logprob"] for e in r.logprob_data]))
    return out


def differences(generated, params, ref, model):
    """``ref`` scores each generated sequence on ``params`` (the weights
    as the seed gives them) in one full forward pass. -> (|difference|
    of every output token's log-probability, the reference's router
    margin at the position that chose it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kw = ref.kwargs_from(model)

    def score(p, ids, n_out):
        logits, margins = ref.logits_and_margins(p, ids[:-1], **kw)
        at = jnp.arange(ids.shape[0] - 1 - n_out, ids.shape[0] - 1)
        return jax.nn.log_softmax(logits, -1)[at, ids[at + 1]], margins[at]

    score = jax.jit(score, static_argnums=2)
    diffs: List[float] = []
    margins: List[float] = []
    for ids, logprobs in generated:
        want, margin = score(params, jnp.asarray(ids, jnp.int32),
                             len(logprobs))
        diffs.extend(np.abs(np.asarray(want, np.float64)
                            - np.asarray(logprobs)).tolist())
        margins.extend(np.asarray(margin, np.float64).tolist())
    return diffs, margins


def dense_report(diffs: List[float],
                 limits: Optional[Dict[str, float]] = None
                 ) -> Dict[str, Any]:
    import statistics

    limits = dict(limits or DENSE_LIMITS)
    report = {"worst": max(diffs), "mean": sum(diffs) / len(diffs),
              "median": statistics.median(diffs), "tokens": len(diffs),
              "limits": limits}
    report["ok"] = (report["worst"] <= limits["worst"]
                    and report["mean"] <= limits["mean"])
    return report


def routed_report(diffs: List[float], margins: List[float],
                  limits: Optional[Dict[str, float]] = None
                  ) -> Dict[str, Any]:
    """The comparison for a model with a router (see ROUTED above):
    every statistic, its limit, and ``ok``. What is taken over all
    tokens (``worst``, ``mean``, ``median``) decides nothing."""
    import statistics

    limits = dict(limits or ROUTED_LIMITS)
    margin = limits["router_margin"]
    decided = [d for d, m in zip(diffs, margins) if m >= margin]
    undecided = [d for d, m in zip(diffs, margins) if m < margin]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
    report = {
        "worst": max(diffs), "mean": mean(diffs),
        "median": statistics.median(diffs), "tokens": len(diffs),
        "router_margin": margin,
        "decided_share": len(decided) / len(diffs),
        "decided_mean": mean(decided),
        "decided_median": statistics.median(decided) if decided else 0.0,
        "decided_over_share": (
            sum(d > limits["over"] for d in decided) / len(decided)
            if decided else 1.0),
        "undecided_worst": max(undecided, default=0.0),
        "undecided_mean": mean(undecided),
        "limits": limits}
    report["ok"] = bool(
        decided
        and report["decided_share"] >= limits["decided_share_at_least"]
        and report["decided_mean"] <= limits["decided_mean"]
        and report["decided_over_share"] <= limits["decided_over_share"]
        and report["decided_median"] <= limits["decided_median"])
    return report


def compared(report: Dict[str, Any]) -> Dict[str, List[float]]:
    """A report's gating statistics, each ``[reading, limit]``
    (``decided_share`` beside its floor), for a run's last line."""
    return {name: [report[name], report["limits"][key]]
            for name, key in GATES.items() if key in report["limits"]}


def control_readings(diffs: List[float], margins: List[float]
                     ) -> Dict[str, Any]:
    """What a calibration reads beside the gating report: the dense
    statistics, and the routed ones at each of CONTROL_MARGINS (the
    margins are +inf where the reference has no router)."""
    dense = dense_report(diffs)
    out = {"dense": {k: dense[k] for k in ("worst", "mean", "median")},
           "routed": []}
    for margin in CONTROL_MARGINS:
        routed = routed_report(diffs, margins,
                               {**ROUTED_LIMITS, "router_margin": margin})
        out["routed"].append({k: routed[k] for k in (
            "router_margin", "decided_share", "decided_mean",
            "decided_median", "decided_over_share")})
    return out


def check_serving(engine_config, reference: str, prompt_lens: List[int],
                  new_tokens: int, seed: int, routed: bool,
                  limits: Optional[Dict[str, float]] = None,
                  control: Optional[Callable] = None,
                  readings: bool = False) -> Dict[str, Any]:
    """Runs in a worker that owns the chip (or, rehearsing, the CPU).
    ``routed`` is the program module's word on the model, ``limits``
    check_limits' of its configuration. ``control`` (one of the program
    module's ``controls``) makes the engine wrong in one named way once
    it is built; the reference keeps the weights as the seed gives
    them. ``readings`` adds control_readings to the report."""
    import gc
    import time

    from ray_tpu.accelerators import jax_backend
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    t0 = time.monotonic()
    engine = ContinuousBatchingEngine(engine_config)
    params = engine.params
    if control is not None:
        control(engine)
    t1 = time.monotonic()
    generated = generate(engine, prompt_lens, new_tokens, seed)
    t2 = time.monotonic()
    # the runtime may hand this worker, chip and all, to the replica:
    # give the engine's cache (and a control's weights) back, before
    # the reference takes its room
    del engine
    gc.collect()
    diffs, margins = differences(generated, params, _reference(reference),
                                 engine_config.model)
    report = (routed_report(diffs, margins, limits) if routed
              else dense_report(diffs, limits))
    # what the check adds to setup_s, by part
    report["seconds"] = {"build": t1 - t0, "generate": t2 - t1,
                         "reference": time.monotonic() - t2}
    if readings:
        report["readings"] = {**control_readings(diffs, margins),
                              "diffs": diffs, "margins": margins}
    report["device"] = jax_backend.device_report()
    del params
    gc.collect()
    return report


def check_training(params, tokens, targets, model, reference: str,
                   program_loss) -> Dict[str, Any]:
    """In the train loop's own process: ``program_loss(params, tokens,
    targets)`` is the loss the step differentiates; tokens/targets are
    [n, S]. The reference walks the n sequences one at a time."""
    import jax

    ref = _reference(reference)
    kw = ref.kwargs_from(model)
    ref_loss = jax.jit(lambda p, t, y: ref.loss(p, t, y, **kw))
    want = sum(float(ref_loss(params, tokens[i], targets[i]))
               for i in range(tokens.shape[0])) / tokens.shape[0]
    got = float(program_loss(params, tokens, targets))
    return {"program": got, "reference": want, "diff": abs(got - want),
            "ok": abs(got - want) <= LOSS_TOL}
