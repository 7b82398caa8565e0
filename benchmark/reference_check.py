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
phase or a dropped layer moves them by tenths to units: the program's
expert layer, which drops tokens over an expert's capacity where the
reference (like Mixtral) drops none, read 1.57 and 0.29 at
Mixtral-8x7B widths and fails this check. The mean loss of a batch
averages the per-token differences out: at 4 layers and 8192 tokens
the two losses differed by 7e-6; the limit is 1e-3.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List

LOGPROB_TOL = 0.15        # worst |difference| of a chosen token's logprob
LOGPROB_MEAN_TOL = 0.04   # mean |difference|
LOSS_TOL = 1e-3           # |program loss - reference loss|, nats


def _reference(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


def check_serving(engine_config, reference: str, prompt_lens: List[int],
                  new_tokens: int, seed: int) -> Dict[str, Any]:
    """Runs in a worker that owns the chip (or, rehearsing, the CPU)."""
    import gc
    import random

    import jax
    import jax.numpy as jnp

    from ray_tpu.accelerators import jax_backend
    from ray_tpu.llm.engine import (ContinuousBatchingEngine,
                                    GenerationRequest)

    ref = _reference(reference)
    engine = ContinuousBatchingEngine(engine_config)
    rng = random.Random(seed)
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=[256] + [rng.randrange(97, 123) for _ in range(n - 1)],
        max_tokens=new_tokens, temperature=0.0, logprobs=0))
        for n in prompt_lens]
    while engine.has_work():
        engine.step()
    kw = ref.kwargs_from(engine_config.model)
    score = jax.jit(lambda p, t: jax.nn.log_softmax(
        ref.logits(p, t, **kw), -1))
    diffs: List[float] = []
    for r in requests:
        if r.error or len(r.output_ids) != new_tokens:
            raise RuntimeError(f"engine request failed: {r.error!r}, "
                               f"{len(r.output_ids)} tokens")
        ids = list(r.prompt_ids) + list(r.output_ids)
        logp = score(engine.params, jnp.asarray(ids[:-1], jnp.int32))
        start = len(r.prompt_ids) - 1
        for j, entry in enumerate(r.logprob_data):
            want = float(logp[start + j, entry["id"]])
            diffs.append(abs(want - float(entry["logprob"])))
    report = {"worst": max(diffs), "mean": sum(diffs) / len(diffs),
              "tokens": len(diffs), "device": jax_backend.device_report()}
    # the runtime may hand this worker, chip and all, to the replica:
    # give the engine's weights and cache back first
    del engine, score, logp
    gc.collect()
    report["ok"] = (report["worst"] <= LOGPROB_TOL
                    and report["mean"] <= LOGPROB_MEAN_TOL)
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
