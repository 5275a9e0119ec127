"""The system under test, driven as ``python -m repro.launch.train`` drives it.

Everything the benchmark takes from the program is here: the architecture
from its registry (with the configuration's overrides), the layout its entry
point picks over the chips present, the trainer (``ElasticTrainer``), its
optimizer state, and the compiled step's memory analysis.  The weights come
from the benchmark (the configuration's reference module makes them from
the seed); this module places them into the program's parameter tree.
What it must know of a model family (which ArchConfig attribute holds each
size, which program parameter is which reference parameter) the reference
module states, so that this module holds nothing of one family.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.launch.train import ElasticTrainer
from repro.optim import optimizers as optim


def make_arch(config: Dict, ref):
    """The program's ArchConfig for ``config``, checked against its sizes
    by the names that the reference module ``ref`` states for its family."""
    arch = configs.get_arch(config["program_arch"])
    over = dict(config.get("program_overrides", {}))
    attn_over = over.pop("attn", None)
    if attn_over:
        over["attn"] = dataclasses.replace(arch.attn, **attn_over)
    arch = dataclasses.replace(arch, **over)
    for attr, want in ref.PROGRAM_FAMILY.items():
        if getattr(arch, attr) != want:
            raise ValueError(f"{arch.name}: {ref.__name__} is the reference of "
                             f"{attr}={want!r}, the program runs "
                             f"{getattr(arch, attr)!r}")
    for key, path in ref.PROGRAM_KEYS.items():
        got = arch
        for p in path:
            got = getattr(got, p)
        if got != config["sizes"][key]:
            raise ValueError(f"{arch.name}: the program runs {key}={got}, the "
                             f"configuration states {config['sizes'][key]}")
    return arch


def make_trainer(arch, traffic: Dict, optimizer: Dict, chips: int, data):
    """The trainer over ``chips`` local devices, laid out as the entry point
    lays it out (``fit_local``, ``derive_n_micro``, default schedule)."""
    shape = ShapeConfig("train", int(traffic["seq_len"]),
                        int(traffic["global_batch"]), "train")
    pcfg = mesh_lib.fit_local(configs.get_parallel(arch.name), pipe=chips)
    pcfg = pcfg.with_(n_micro=configs.derive_n_micro(shape, pcfg))
    ocfg = optim.OptimizerConfig(**optimizer)
    return ElasticTrainer(arch, pcfg, shape, ocfg, data=data,
                          dtype=jnp.bfloat16)


def _leaf_map(tr, weights: Dict, leaves: Dict) -> Dict[Tuple[str, ...], str]:
    proto = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(proto)[0]:
        key = tuple(p.key for p in path)
        name = leaves.get(key)
        if name is None or name not in weights:
            raise ValueError(f"program parameter {'/'.join(key)} has no "
                             "counterpart in the reference")
        out[key] = name
    return out


def to_program_tree(tr, weights: Dict, leaves: Dict):
    """The reference's parameters in the program's tree (``leaves``: program
    path -> reference name): per-layer ones stacked ``[n_stages,
    L_per_stage, ...]`` by the program's layout, padding slots zero."""
    slots = tr.model.layout.slot_layer                      # [n_stages, L]
    tree = {}
    for key, name in _leaf_map(tr, weights, leaves).items():
        w = weights[name]
        if key[0] == "stages":
            w = jnp.where((slots >= 0).reshape(slots.shape + (1,) * (w.ndim - 1)),
                          w[np.maximum(slots, 0)], 0).astype(w.dtype)
        node = tree
        for k in key[:-1]:
            node = node.setdefault(k, {})
        node[key[-1]] = w
    return tree


def make_state(tr, init_weights, key, leaves: Dict):
    """Parameters and optimizer state, made on the device in one jitted
    call from ``key`` and placed as the trainer places its own."""
    def build(k):
        params = to_program_tree(tr, init_weights(k), leaves)
        return {"params": params, "opt": optim.init(tr.ocfg, params)}
    return tr.make_state(jax.jit(build)(key))


def per_layer(tr, tree, leaves: Dict) -> Dict[str, np.ndarray]:
    """A per-slot tree of numbers -> the reference's names, per layer."""
    slots = tr.model.layout.slot_layer
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = tuple(p.key for p in path)
        name = leaves[key]
        v = np.asarray(v)
        if key[0] == "stages":
            layer = np.full(int(slots.max()) + 1, np.nan)
            layer[slots[slots >= 0]] = v[slots >= 0]
            v = layer
        out[name] = v
    return out


def _slot_norms(tree):
    """Norm of every leaf; per ``[stage, slot]`` for the stacked layers."""
    def norm(path, x):
        axes = tuple(range(2 if path[0].key == "stages" else 0, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))
    return jax.tree_util.tree_map_with_path(norm, tree)


def moment_norms(tr, opt_state, leaves: Dict) -> Dict[str, np.ndarray]:
    """Per-parameter, per-layer norms of Adam's first moment."""
    return per_layer(tr, jax.device_get(jax.jit(_slot_norms)(opt_state.mu)),
                     leaves)


def change_norms(tr, opt_state, init_weights, key, leaves: Dict
                 ) -> Dict[str, np.ndarray]:
    """Per-parameter, per-layer norms of the master weights' change since
    ``init_weights(key)``."""
    def f(master, k):
        start = to_program_tree(tr, init_weights(k), leaves)
        return _slot_norms(jax.tree.map(
            lambda m, s: m - s.astype(jnp.float32), master, start))
    return per_layer(tr, jax.device_get(jax.jit(f)(opt_state.master, key)),
                     leaves)


def compiled_step(tr, state, step: int):
    """The trainer's compiled step.  Called after the step has run, it finds
    the executable in memory: it lowers again and compiles nothing."""
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch_at(step).items()}
    with jax.set_mesh(tr.mesh):
        return tr.jit_step.lower(state["params"], state["opt"],
                                 batch).compile()


def peak_bytes(compiled) -> int:
    """Arguments + temporaries + outputs - aliased bytes of one device."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)
