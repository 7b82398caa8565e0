"""The seam between the serving engine and a model family
(ray_tpu/models/family.py): the table, the one rule for a cache's
bytes, and the Llama family as a row of the contract every family is
held to (tests/family_contract.py), against its plain reference
(benchmark/reference/mistral.py) in float32 at tiny sizes: hidden 64,
two layers, 4 heads over 2 KV heads, vocabulary 512. The engine's
other step programs, which are this family's alone, are
tests/test_llm.py's."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import mistral as reference
from family_contract import (  # noqa: F401 the contract, over ROW
    Row, Variant, pytest_generate_tests, row,
    test_embed_and_fail_all_go_through_the_family,
    test_engine_prefill_then_decode_matches_the_reference,
    test_forward_matches_the_reference,
    test_padding_leaves_the_entry_and_the_counts_of_the_prompt_alone,
    test_requests_admitted_at_different_steps_equal_their_solo_outputs)
from ray_tpu.models import family as family_mod
from ray_tpu.models.family import ModelFamily, family_of
from ray_tpu.models.granite import GraniteConfig
from ray_tpu.models.jamba import JambaConfig
from ray_tpu.models.lfm2 import Lfm2Config
from ray_tpu.models.llama import LlamaConfig, llama_forward
from ray_tpu.models.mla import MlaConfig

CFG = LlamaConfig.tiny(vocab_size=512)

# the contract but for its refusals: this family's cache is the pair of
# keys and values the engine's other programs were written over, and
# nothing is refused for it (``dense_only`` is "")
ROW = Row(reference=reference, forward=llama_forward,
          variants={"": Variant(CFG)})


def test_the_table_names_a_module_that_ends_with_its_family():
    import importlib
    for name, module in family_mod._FAMILIES.items():
        held = importlib.import_module(module)
        assert isinstance(held.FAMILY, ModelFamily)
        assert getattr(held, name).__name__ == name
        assert family_of(getattr(held, name).tiny()) is held.FAMILY
    assert family_of(CFG).dense_only == ""
    with pytest.raises(TypeError, match="no model family for a int"):
        family_of(3)


@pytest.mark.parametrize("config,kinds", [
    (LlamaConfig, {"kv": ("0", "1"), "recurrent": ()}),
    (JambaConfig, {"kv": ("k", "v"), "recurrent": ("ssm", "conv")}),
    (GraniteConfig, {"kv": ("k", "v"), "recurrent": ("ssm", "conv")}),
    (Lfm2Config, {"kv": ("k", "v"), "recurrent": ("conv",)}),
    (MlaConfig, {"latent": ("latent",)})], ids=lambda v: getattr(
        v, "__name__", ""))
def test_cache_bytes_are_told_by_the_leaves_names(config, kinds):
    """One rule for every family (``ModelFamily.init_cache``): ``k`` and
    ``v`` (and the Llama family's pair) are "kv", ``latent`` is
    "latent", every other leaf "recurrent", which a cache of keys and
    values tells even where it is 0."""
    cfg = config.tiny()
    family = family_of(cfg)
    cache = jax.eval_shape(lambda: family.init_cache(cfg, 3, 128))
    leaves = {jax.tree_util.keystr(path, simple=True, separator="/"): leaf
              for path, leaf in jax.tree_util.tree_leaves_with_path(cache)}
    assert sorted(leaves) == sorted(n for names in kinds.values()
                                    for n in names)
    assert family.cache_bytes(cache) == {
        kind: sum(leaves[n].size * leaves[n].dtype.itemsize for n in names)
        for kind, names in kinds.items()}
    assert list(family.cache_bytes(cache)) == list(kinds)


def test_hidden_is_the_forward_before_the_head():
    """``ModelFamily.hidden`` is the family's forward with
    ``return_hidden``: the final-norm states the head would read."""
    from family_contract import weights
    params = weights(CFG)
    tokens = jnp.arange(12, dtype=jnp.int32)[None]
    hidden = family_of(CFG).hidden(params, tokens, CFG)
    assert hidden.shape == (1, 12, CFG.dim)
    logits = llama_forward(params, tokens, CFG)
    assert float(jnp.abs(hidden @ params["lm_head"] - logits).max()) < 1e-4
