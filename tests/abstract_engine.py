"""An engine around shapes, for a TPU v5e with no chip: the serving
engine built over ``jax.ShapeDtypeStruct``s on the compile-only ``v5e:2x2``
topology (no weights and no cache are made), and its ``decode`` and
``prefill`` programs lowered from abstract arguments. tests/test_tpu_compile.py
builds every family's compile test on it.

Run as a script it writes what it lowered, for the five serving
configurations under benchmark/configs/ at the layers, batch and
sequence their files give and the smallest and largest bucket their
cells' prompts fall into:

    python tests/abstract_engine.py --out DIR [--tree CHECKOUT] [CONFIG ...]
    python tests/abstract_engine.py --compare DIR_A DIR_B

``--out`` leaves ``DIR/<config>/<program>.mlir`` and ``kernels.json``
(program -> Pallas kernels); ``--tree`` lowers another checkout's
programs with this file (a parent commit's, unpacked beside: it need
not have this file); ``--compare`` prints, program by program, ``equal``
or the first line at which two such directories differ, and exits 1 if
any does. Lowering needs no chip and compiles nothing; a text is
0.1-0.8 MB. This is how a PR that folds model code shows that every
cell's programs are the parent's.

A text is ``lowered.as_text()`` (no source locations) but for the Pallas
kernels' bodies: those are serialized WITH the Python call stack of
their call site, file paths and line numbers, which differ between two
checkouts whatever the programs do. ``without_locations`` parses each
body and writes its operations without them, at the end of the text,
and a digest of that in the body's place.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import dataclasses
import hashlib
import importlib
import json
import os
import re
import sys
from typing import Any, List
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TOPOLOGY = "v5e:2x2"


def v5e_devices():
    """The compile-only topology's devices (libtpu ships the compiler);
    raises where it cannot be had."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices


def mesh_of(devices, fsdp: int = 1):
    from ray_tpu.parallel.mesh import AXIS_ORDER
    shape = tuple(fsdp if a == "fsdp" else 1 for a in AXIS_ORDER)
    return Mesh(np.asarray(devices[:fsdp]).reshape(shape), AXIS_ORDER)


def on(mesh, spec, *shape_dtype):
    return jax.ShapeDtypeStruct(*shape_dtype,
                                sharding=NamedSharding(mesh, spec))


def replicated(mesh, tree):
    """``tree``'s shapes and types as abstract arrays on ``mesh``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, P())),
        tree)


@contextlib.contextmanager
def lowering_for_tpu():
    """The host backend is the CPU; the programs are lowered for the
    topology's devices, so kernel selection must answer for those."""
    from ray_tpu.accelerators import jax_backend
    was = jax_backend.on_tpu
    jax_backend.on_tpu = lambda: True
    try:
        yield
    finally:
        jax_backend.on_tpu = was


@dataclasses.dataclass
class AbstractEngine:
    """``engine``: a ContinuousBatchingEngine whose params and cache are
    shapes; ``params`` / ``cache`` (the list of the cache's leaves) /
    ``counts`` (None for a family that counts nothing on the device):
    what its programs are lowered with."""
    engine: Any
    mesh: Any
    params: Any
    cache: List[Any]
    counts: Any

    @property
    def weight_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.params))

    def lower_decode(self, want_lp: bool = False):
        """The engine's own ``decode`` (``decode_lp``) program: the
        cache donated, the per-slot inputs and the sampler's counter
        one packed [7, B] int32 state."""
        config = self.engine.config
        batch, vocab = config.max_batch, config.model.vocab_size
        counts = () if self.counts is None else (self.counts,)
        return self.engine._decode.lower(
            self.params, self.cache, on(self.mesh, P(), (7, batch), jnp.int32),
            on(self.mesh, P(), (2,), jnp.uint32), None,
            on(self.mesh, P(), (batch, vocab), jnp.float32), *counts,
            want_lp=want_lp)

    def lower_prefill(self, bucket: int):
        """The engine's ``prefill_<bucket>`` program: one prompt padded
        to the bucket and its true length, traced."""
        counts = () if self.counts is None else (self.counts,)
        return self.engine._prefill.lower(
            self.params, on(self.mesh, P(), (1, bucket), jnp.int32),
            on(self.mesh, P(), (), jnp.int32), None, *counts)


def abstract_engine(model, batch: int, seq: int, devices=None
                    ) -> AbstractEngine:
    """The engine of ``EngineConfig(model=model, max_batch=batch,
    max_seq=seq)`` over abstract weights and an abstract cache on one
    chip of the topology. Call it, and lower, under ``lowering_for_tpu``
    (tests/test_tpu_compile.py's autouse fixture does the same)."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.models.family import family_of
    family = family_of(model)
    mesh = mesh_of(v5e_devices() if devices is None else devices)
    params = replicated(mesh, jax.eval_shape(
        lambda key: family.init(key, model), jax.random.PRNGKey(0)))
    cache = jax.tree.leaves(replicated(mesh, jax.eval_shape(
        lambda: family.init_cache(model, batch, seq))))
    counts = (on(mesh, P(), (len(family.expert_counts),), jnp.uint32)
              if family.expert_counts else None)
    cls = engine_mod.ContinuousBatchingEngine
    # an engine around shapes: no weights and no cache are made here
    with mock.patch.multiple(
            cls, _fresh_cache=lambda self, model: cache,
            _fresh_bias=lambda self: (None, None),
            _fresh_expert_counts=lambda self: None):
        engine = cls(engine_mod.EngineConfig(
            model=model, max_batch=batch, max_seq=seq), params=params)
    return AbstractEngine(engine, mesh, params, cache, counts)


# -- as a script: the serving cells' programs, written out ----------------

def _serving_cells(root: str):
    """(configuration name, its file's dict, the cell's smallest and
    largest prompt in tokens) for every configuration under
    benchmark/configs/ that BENCHMARK.json serves."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        declared = json.load(f)
    seen = {}
    for cell in declared["workloads"]:
        with open(os.path.join(root, "benchmark", "configs",
                               cell["config"] + ".json")) as f:
            config = json.load(f)
        with open(os.path.join(root, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            prompts = json.load(f).get("prompt_bytes")
        if "serving" in config and prompts:
            # a byte is a token, and the tokenizer puts one before them
            seen.setdefault(cell["config"], (config, prompts["min"] + 1,
                                             prompts["max"] + 1))
    return [(name, *rest) for name, rest in seen.items()]


def without_locations(text: str) -> str:
    """A lowered program's text with every Pallas kernel's body (base64
    of MLIR bytecode that carries its call site's stack) replaced by a
    digest of the body's operations printed without locations, and
    those printed bodies after the program, each once."""
    from jax._src.lib.mlir import ir
    printed = {}

    def digest(match):
        context = ir.Context()
        context.allow_unregistered_dialects = True
        body = ir.Module.parse(base64.b64decode(match.group(1)),
                               context).operation.get_asm(
                                   enable_debug_info=False)
        name = hashlib.sha256(body.encode()).hexdigest()[:16]
        printed[name] = body
        return f'\\22body\\22: \\22{name}\\22'

    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', digest, text)
    return text + "".join(f"\n// kernel body {name}\n{body}"
                          for name, body in sorted(printed.items()))


def write_programs(out: str, root: str, only: List[str]) -> None:
    from ray_tpu.accelerators import jax_backend
    for name, config, shortest, longest in _serving_cells(root):
        if only and name not in only:
            continue
        sizes = config["serving"]
        program = importlib.import_module(
            "benchmark.programs." + config["program"])
        model = program.serving_model(config, sizes["max_seq"], False)
        where = os.path.join(out, name)
        os.makedirs(where, exist_ok=True)
        kernels = {}
        with lowering_for_tpu():
            built = abstract_engine(model, sizes["max_batch"],
                                    sizes["max_seq"])
            programs = {"decode": built.lower_decode()}
            for prompt in (shortest, longest):
                bucket = built.engine._bucket_len(prompt)
                programs[f"prefill_{bucket}"] = built.lower_prefill(bucket)
        for title, lowered in programs.items():
            text = lowered.as_text()
            kernels[title] = jax_backend.pallas_kernels(text)
            text = without_locations(text)
            with open(os.path.join(where, title + ".mlir"), "w") as f:
                f.write(text)
            print(f"{name} {title}: {len(text)} bytes, "
                  f"{[k.split('(')[0] for k in kernels[title]]}", flush=True)
        with open(os.path.join(where, "kernels.json"), "w") as f:
            json.dump(kernels, f, indent=1, sort_keys=True)


def compare(dir_a: str, dir_b: str) -> int:
    """Prints a table row a program; -> the number that differ."""
    differ = 0
    print("| configuration | program | text | kernels |")
    print("|---|---|---|---|")
    for name in sorted(os.listdir(dir_a)):
        with open(os.path.join(dir_a, name, "kernels.json")) as f:
            kernels_a = json.load(f)
        with open(os.path.join(dir_b, name, "kernels.json")) as f:
            kernels_b = json.load(f)
        for title in kernels_a:
            with open(os.path.join(dir_a, name, title + ".mlir")) as f:
                a = f.read().splitlines()
            with open(os.path.join(dir_b, name, title + ".mlir")) as f:
                b = f.read().splitlines()
            at = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                      None if len(a) == len(b) else min(len(a), len(b)))
            text = (f"equal ({len(a)} lines)" if at is None
                    else f"differs at line {at + 1}")
            same = kernels_a[title] == kernels_b.get(title)
            differ += at is not None or not same
            print(f"| {name} | {title} | {text} | "
                  f"{'equal' if same else 'differ'}: "
                  f"{', '.join(k.split('(')[0] for k in kernels_a[title])} |")
    return differ


def main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", help="write the lowered programs here")
    parser.add_argument("--tree", help="the checkout whose programs to "
                        "lower (default: this file's)")
    parser.add_argument("--compare", nargs=2, metavar="DIR")
    parser.add_argument("configs", nargs="*")
    args = parser.parse_args(argv)
    if args.compare:
        return 1 if compare(*args.compare) else 0
    if not args.out:
        parser.error("--out DIR or --compare DIR_A DIR_B")
    root = os.path.abspath(args.tree or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir))
    sys.path.insert(0, root)
    print(f"the serving programs of {root}", flush=True)
    write_programs(args.out, root, args.configs)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
