"""Smoke run of the training path on a TPU at published widths.

Trains full-width smollm-360m (32 layers, d_model 960, vocab 49152, bf16;
weights random from a seed) on synthetic tokens through the trainer that
``python -m repro.launch.train`` drives (``launch.train.ElasticTrainer``,
which jits ``launch.steps.build_train_step``).  It is a smoke run, not a
benchmark: the times it prints are for orientation only.

    python chip_smoke.py             # one chip: 5 steps 1f1b + 5 steps gpipe
    python chip_smoke.py --chips 4   # four chips: two pipe=4 1F1B steps against
                                     # the same steps at pipe=1 on chip 0

It exits non-zero when JAX finds no TPU.  The last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticLM  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.train import ElasticTrainer  # noqa: E402
from repro.optim import optimizers as optim  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
BATCH, SEQ = 8, 2048
STEPS = 5
# Four-chip phase: relative bounds on how far pipe=4 may sit from pipe=1
# over two steps from the same weights and batches.  The two layouts round
# bf16 differently; on four virtual CPU devices at full width (batch
# 8 x 64) the largest differences were 9.2e-4 (loss), 1.1e-3 (gradient
# norm) and 6.1e-6 (parameter norm).  The bounds allow about 5x that.
LOSS_RTOL = 5e-3
GRAD_RTOL = 5e-3
NORM_RTOL = 5e-5


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def make_trainer(pipe, schedule, *, seq=SEQ, n_micro=0):
    """The trainer the entry point builds over ``pipe`` local devices."""
    arch = configs.get_arch(ARCH)
    shape = ShapeConfig("train", seq, BATCH, "train")
    pcfg = mesh_lib.fit_local(configs.get_parallel(ARCH), pipe=pipe)
    pcfg = pcfg.with_(schedule=schedule, n_micro=n_micro
                      or configs.derive_n_micro(shape, pcfg))
    # the optimizer settings `repro.launch.train` uses for a bf16 run
    ocfg = optim.OptimizerConfig(lr=3e-4, warmup_steps=STEPS,
                                 total_steps=2 * STEPS,
                                 dynamic_loss_scale=True)
    data = SyntheticLM(DataConfig(seed=SEED, vocab=arch.vocab, seq_len=seq,
                                  global_batch=BATCH), arch)
    return ElasticTrainer(arch, pcfg, shape, ocfg, data=data,
                          dtype=jnp.bfloat16)


def train_phase(schedule, *, seq=SEQ, steps=STEPS):
    """``steps`` steps on one device; returns losses and timings."""
    tr = make_trainer(1, schedule, seq=seq)
    state = tr.make_state(None)
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch_at(0).items()}
    t0 = time.perf_counter()
    with jax.set_mesh(tr.mesh):
        compiled = tr.jit_step.lower(state["params"], state["opt"],
                                     batch).compile()
    compile_s = time.perf_counter() - t0
    pallas = "tpu_custom_call" in compiled.as_text()
    del compiled, batch
    losses, step_s = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = tr.step(state, i)
        jax.block_until_ready(state)
        step_s.append(time.perf_counter() - t0)
        losses.append(metrics["loss"])
    return {"schedule": schedule, "pipe": tr.pcfg.pipe,
            "n_micro": tr.pcfg.n_micro, "params": tr.arch.total_params(),
            "losses": losses, "skipped": metrics.get("skipped", 0),
            "compile_s": compile_s, "step_s": step_s,
            "pallas_in_hlo": pallas}


def pipeline_steps(pipe, *, seq=SEQ, n_micro=0, steps=2):
    """1F1B steps over ``pipe`` devices.  Returns per-step losses and
    gradient norms, the parameter norm after the last step and, per
    stage-parameter leaf, the device of each stage's slice.  The second
    step's loss is taken after the first update, so it checks the first
    step's gradients too."""
    tr = make_trainer(pipe, "1f1b", seq=seq, n_micro=n_micro)
    state = tr.make_state(None)
    losses, gnorms = [], []
    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = tr.step(state, i)
        losses.append(metrics["loss"])
        gnorms.append(metrics["grad_norm"])
    norm = float(optim.global_norm(state["params"]))
    placement = [{s.index[0].start or 0: str(s.device)
                  for s in leaf.addressable_shards}
                 for leaf in jax.tree.leaves(state["params"]["stages"])]
    return {"pipe": pipe, "n_micro": tr.pcfg.n_micro, "losses": losses,
            "grad_norms": gnorms, "param_norm": norm,
            "seconds": time.perf_counter() - t0, "stage_devices": placement}


def check_one_chip(runs, vocab):
    for r in runs:
        assert all(math.isfinite(x) for x in r["losses"]), r["losses"]
        assert abs(r["losses"][0] - math.log(vocab)) < 1.0, \
            (r["schedule"], r["losses"][0], math.log(vocab))
        assert r["pallas_in_hlo"], f"no Pallas kernel in {r['schedule']} step"


def check_four_chips(pn, p1, n=4):
    """Returns the largest relative differences of the losses, of the
    gradient norms and of the final parameter norm."""
    def rel(a, b):
        assert math.isfinite(a) and math.isfinite(b), (a, b)
        return abs(a - b) / abs(b)
    dl = max(rel(a, b) for a, b in zip(pn["losses"], p1["losses"]))
    dg = max(rel(a, b) for a, b in zip(pn["grad_norms"], p1["grad_norms"]))
    dn = rel(pn["param_norm"], p1["param_norm"])
    assert dl <= LOSS_RTOL, f"losses pipe={n} {pn['losses']} vs {p1['losses']}"
    assert dg <= GRAD_RTOL, \
        f"grad norms pipe={n} {pn['grad_norms']} vs {p1['grad_norms']}"
    assert dn <= NORM_RTOL, \
        f"param norm pipe={n} {pn['param_norm']} vs {p1['param_norm']}"
    for per_leaf in pn["stage_devices"]:
        assert sorted(per_leaf) == list(range(n)), per_leaf
        assert len(set(per_leaf.values())) == n, per_leaf
    return dl, dg, dn


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip pipeline phase")
    args = ap.parse_args()
    enable_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devices[0].platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devices)} "
                 "TPU devices present")
    dev = devices[0]
    log(f"smoke run, not a benchmark: {ARCH} at full width on "
        f"{len(devices)} x {dev.device_kind}")

    if args.chips == 1:
        runs = [train_phase("1f1b"), train_phase("gpipe")]
        for r in runs:
            log(json.dumps(r))
        check_one_chip(runs, configs.get_arch(ARCH).vocab)
    else:
        p4 = pipeline_steps(4, n_micro=8)
        log(json.dumps(p4))
        p1 = pipeline_steps(1, n_micro=8)
        log(json.dumps(p1))
        dl, dg, dn = check_four_chips(p4, p1)
        log(f"pipe=4 vs pipe=1 relative differences: loss {dl}, grad norm "
            f"{dg}, param norm {dn}; stage parameters on 4 devices")
    log(f"peak_bytes_in_use {(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
