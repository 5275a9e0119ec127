"""The program's tracing: device scopes in the compiled train step's HLO
metadata (``repro.scopes``) and host spans in ``ElasticTrainer.step``.

The scopes are read back as the benchmark reads them (``bench/scopes.py``):
by the ``op_name`` of each instruction of the compiled step.
"""
import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs, scopes
from repro.configs.base import ShapeConfig
from repro.launch import mesh as mesh_lib, steps
from repro.launch.train import ElasticTrainer
from repro.models.lm import LMModel
from repro.optim import optimizers as optim

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import scopes as reader, trace as trace_lib  # noqa: E402

ARCH = "smollm-360m"
SHAPE = ShapeConfig("train", 16, 4, "train")
PRODUCTS = ("dot", "custom-call", "convolution")
LAYERS = ("attn", "mlp", "head_loss", "embed", "optimizer")


def _compile(schedule):
    arch = configs.smoke_arch(ARCH)
    pcfg = configs.smoke_parallel(ARCH).with_(schedule=schedule)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    ocfg = optim.OptimizerConfig()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: optim.init(ocfg, p), params)
    with jax.set_mesh(mesh):
        fn = steps.build_train_step(model, pcfg, mesh, SHAPE, ocfg)
        return jax.jit(fn).lower(params, opt, model.input_specs(SHAPE)).compile()


_COMPILED = {}


def _step(schedule):
    """The compiled step, its instructions' ``op_name``s and lines."""
    if schedule not in _COMPILED:
        compiled = _compile(schedule)
        hlo = compiled.as_text()
        _COMPILED[schedule] = (compiled, reader.op_names(hlo),
                               trace_lib.instruction_lines(hlo))
    return _COMPILED[schedule]


def _products(schedule):
    """(layer scopes, phase) of every matrix product of the step."""
    _, names, lines = _step(schedule)
    return [([s for s in reader.scopes(names[n]) if s in LAYERS],
             reader.phase(names[n]))
            for n, line in lines.items() if reader._opcode(line) in PRODUCTS]


def test_the_benchmark_reads_the_programs_scope_names():
    assert set(reader.NAMES) == set(scopes.NAMES)
    assert set(reader.MODEL) <= set(scopes.NAMES)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_every_matrix_product_carries_one_layer_scope(schedule):
    products = _products(schedule)
    assert products
    assert all(len(layers) == 1 for layers, _ in products), \
        [p for p in products if len(p[0]) != 1]


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_backward_and_recompute_are_told_apart(schedule):
    """``remat="full"`` recomputes every forward product once; its
    backward has at least one product per forward one."""
    count = {}
    for layers, phase in _products(schedule):
        key = (layers[0], phase)
        count[key] = count.get(key, 0) + 1
    for layer in ("attn", "mlp", "head_loss"):
        fwd = count.get((layer, "forward"), 0)
        assert fwd > 0
        assert count.get((layer, "recompute"), 0) == fwd
        assert count.get((layer, "backward"), 0) >= fwd


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipe_scope_labels_the_tick_loop(schedule):
    """The tick loop's own work (its stash writes, inside the loop's
    body) is under ``pipe`` and no model scope; the fused executor's task
    branches carry their kind."""
    _, names, lines = _step(schedule)
    runtime = [n for n, op in names.items() if "pipe" in reader.scopes(op)
               and not set(reader.scopes(op)) & set(reader.MODEL)]
    assert any(reader._opcode(lines[n]) == "dynamic-update-slice"
               and "/while/body/" in names[n] for n in runtime)
    kinds = {s for op in names.values() for s in reader.scopes(op)}
    if schedule == "1f1b":
        assert {"pipe_f", "pipe_b", "grad_reduce"} <= kinds
    else:
        whiles = [reader.scopes(names[n]) for n, line in lines.items()
                  if reader._opcode(line) == "while"]
        assert ("pipe",) in whiles                   # the tick loop itself
        assert not kinds & {"pipe_f", "pipe_b"}


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_scopes_leave_the_compiled_step_unchanged(schedule, monkeypatch):
    scoped = _step(schedule)[0]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compile(schedule)
    assert not any(reader.scopes(op) for op in
                   reader.op_names(plain.as_text()).values())

    def count(c):
        return len(trace_lib.instruction_lines(c.as_text()))

    def memory(c):
        m = c.memory_analysis()
        return (m.argument_size_in_bytes, m.output_size_in_bytes,
                m.temp_size_in_bytes, m.alias_size_in_bytes)
    assert count(scoped) == count(plain)
    assert memory(scoped) == memory(plain)


def _host_spans(directory, prefix="train."):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_lib.find_xplane(directory))
    return sorted((trace_lib.Event(e.name, int(e.start_ns), int(e.duration_ns))
                   for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith(prefix)), key=lambda e: e.start)


@pytest.fixture(scope="module")
def trainer():
    arch = configs.smoke_arch(ARCH)
    pcfg = configs.smoke_parallel(ARCH).with_(n_micro=2)
    tr = ElasticTrainer(arch, pcfg, SHAPE, optim.OptimizerConfig())
    state, _ = tr.step(tr.make_state(None), 0)          # compiles
    return tr, state


def test_trainer_step_writes_its_host_spans_in_order(trainer, tmp_path):
    tr, state = trainer
    with jax.profiler.trace(str(tmp_path)):
        tr.step(state, 1)
    spans = _host_spans(str(tmp_path))
    names = [e.name for e in spans]
    assert names == ["train.step", "train.batch", "train.put",
                     "train.dispatch", "train.readback"]
    outer, inner = spans[0], spans[1:]
    assert all(outer.start <= e.start and e.end <= outer.end for e in inner)
    assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))


def test_checkpoint_save_writes_its_host_span(trainer, tmp_path):
    tr, _ = trainer
    sup = tr.supervisor(str(tmp_path / "ckpt"))
    state = tr.make_state(None)
    with jax.profiler.trace(str(tmp_path / "trace")):
        sup._save(1, state)
    sup.ckpt.wait()
    assert [e.name for e in _host_spans(str(tmp_path / "trace"))] == \
        ["train.ckpt_save"]
