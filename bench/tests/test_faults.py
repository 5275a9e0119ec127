"""A whole run, with the chip look skipped, at a size a CPU holds: sound,
it is correct; with the timed path broken underneath, it is not; and the
control, the reference with fp8 matrix products, fails the comparison.

The cell is smollm-360m's with every width cut (the chip runs the real
ones: ``bench/calibrate.py``), held in turn to the limits of each cell of
``BENCHMARK.json``.
"""
import functools
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness, program
from bench.configs import llama
from bench.traffic import ZipfRows

CELLS = [w["name"] for w in harness.load_json("BENCHMARK.json")["workloads"]]
TRAFFIC = {"global_batch": 4, "seq_len": 64, "zipf_a": 1.2, "doc_len": 32}
SEED = 2 ** 31 + 19


def tiny_config():
    c = json.loads(json.dumps(harness.load_json("bench", "configs", "smollm-360m.json")))
    c["program_overrides"] = {"n_layers": 2, "d_model": 128, "d_ff": 256, "vocab": 512,
                              "attn": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 32}}
    c["sizes"].update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
                      vocab_size=512, num_attention_heads=4, num_key_value_heads=2,
                      head_dim=32)
    return c


@pytest.fixture(params=CELLS)
def limits(request):
    return harness.load_json("bench", "workloads", request.param + ".json")["limits"]


def tiny_cell(limits):
    return harness.Cell("tiny", 1, tiny_config(), TRAFFIC, limits,
                        harness.load_json("bench", "optimizer.json"), [])


def run(limits, seconds=0.5):
    return harness.run(tiny_cell(limits), seed=SEED, seconds=seconds,
                       trace=False, t0=time.time())


def plant(monkeypatch, broken_step):
    """Replace the trainer's jitted step by ``broken_step(trainer, make)``."""
    make = program.make_trainer

    def make_broken(arch, traffic, optimizer, chips, data):
        tr = make(arch, traffic, optimizer, chips, data)
        step = tr.jit_step
        tr.jit_step = broken_step(tr, functools.partial(
            make, arch, optimizer=optimizer, chips=chips, data=data), traffic)
        tr.jit_step.lower = step.lower      # the memory analysis reads it
        return tr
    monkeypatch.setattr(program, "make_trainer", make_broken)


def test_sound_run_is_correct(limits):
    r = run(limits)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert list(r["checks"])[:-1] == [k for k in check.NAMES if k in limits]


def test_state_left_unchanged_is_not_correct(monkeypatch, limits):
    def unchanged(tr, make, traffic):
        step = tr.jit_step

        def f(p, o, b):
            copy = functools.partial(jax.tree.map, jnp.copy)
            _, _, m = step(copy(p), copy(o), b)
            return p, o, m
        return f
    plant(monkeypatch, unchanged)
    r = run(limits)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] > limits["change_gap"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch, limits):
    def half(tr, make, traffic):
        small = make(dict(traffic, global_batch=traffic["global_batch"] // 2))

        def f(p, o, b):
            return small.jit_step(p, o, {k: v[:v.shape[0] // 2] for k, v in b.items()})
        return f
    plant(monkeypatch, half)
    r = run(limits)
    assert not r["correct"]


def test_control_fails_the_comparison(limits):
    """The reference in fp8 put in the program's place fails a limit."""
    cell = tiny_cell(limits)
    sizes = cell.config["sizes"]
    feed = ZipfRows(TRAFFIC, sizes["vocab_size"], SEED)
    batches = [(b["tokens"], b["labels"]) for b in
               (feed.batch_at(i) for i in range(harness.REF_STEPS))]
    key = jnp.asarray(harness.seed_key(SEED))
    ref = llama.train(sizes, cell.optimizer, llama.FP32, key, batches)
    ctl = llama.train(sizes, cell.optimizer, llama.FP8, key, batches)
    correct, rows = check.judge(check.gaps(ctl, ref), limits)
    assert not correct, rows


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    """A traced run whose per-layer metric, listed for the cell, finds
    nothing to read (here: no Pallas kernel on the CPU) prints no result."""
    cell = tiny_cell({})
    cell.per_layer = ["flash_attn_fwd_roofline"]
    with pytest.raises(SystemExit, match="flash_attn_fwd_roofline"):
        harness.run(cell, seed=SEED, seconds=0.2, trace=True, t0=time.time())
