"""What the families' ``controls`` share: ways to make a built engine
wrong that ask only for the serving cache's pytree (ModelFamily's, a
dict of leaves by name)."""

from __future__ import annotations


def state_bf16(engine) -> None:
    """After every ``engine.step()`` the cache's ``ssm`` leaf (the
    recurrent state, kept in float32) is rounded to bfloat16's 8
    exponent and 7 mantissa bits: the state as a program would hold it
    that kept it in the narrower type, in every slot, after its prefill
    and after each decode step. ``reduce_precision`` and not a cast
    there and back, which the TPU's compiler takes out as excess
    precision it may keep (my chip runs, PR 59: the cast pair read the
    sound program's numbers to the last digit)."""
    import jax

    # the cache is the list of the pytree's leaves: where the state lies
    at = jax.tree.unflatten(engine._cache_def,
                            list(range(len(engine.cache))))["ssm"]
    narrow = jax.jit(lambda x: jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7), donate_argnums=(0,))
    step = engine.step

    def rounded_step():
        n = step()
        cache = list(engine.cache)
        cache[at] = narrow(cache[at])
        engine.cache = cache
        return n

    engine.step = rounded_step
