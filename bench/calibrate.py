"""Readings that a cell's limits for ``correct`` are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... \\
        [--faults 3] [--out <file>.jsonl]

In one process, for every seed: the program's first steps through the
trainer against the float32 reference (the lower readings); and for the
first ``--faults`` seeds also the control, the reference with fp8 matrix
products, and a planted fault, the reference on half of each batch with
the mean taken over the rest (the upper readings).  A state left unchanged
reads 1 on ``change_gap`` by definition and needs no run.  Each seed's
gaps go to ``--out`` as one JSON line; the summary is printed last.
Benchmark runs never run this.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def calibrate(cell, seeds, faults: int, out=None):
    import jax
    import jax.numpy as jnp
    from bench import check, harness, program
    from bench.traffic import ZipfRows
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ref = cell.reference()
    sizes = cell.config["sizes"]
    init = functools.partial(ref.init_weights, c=sizes)
    tr = program.make_trainer(program.make_arch(cell.config, ref), cell.traffic,
                              cell.optimizer, cell.chips, None)
    rows = []
    for n, seed in enumerate(seeds):
        tr.data = ZipfRows(cell.traffic, sizes["vocab_size"], seed)
        key = jnp.asarray(harness.seed_key(seed))
        t = time.perf_counter()
        state, prog = harness.first_steps(tr, init, key, cell.optimizer,
                                          ref.PROGRAM_LEAVES)
        jax.block_until_ready(state)
        del state
        gc.collect()
        t_prog = time.perf_counter() - t
        batches = [tr.data.batch_at(i) for i in range(harness.REF_STEPS)]
        full = [(b["tokens"], b["labels"]) for b in batches]
        t = time.perf_counter()
        fp32 = ref.train(sizes, cell.optimizer, ref.FP32, key, full)
        row = {"seed": seed, "program": check.gaps(prog, fp32),
               "losses": {"program": prog["losses"], "reference": fp32["losses"]},
               "seconds": {"program": t_prog,
                           "reference": time.perf_counter() - t}}
        if n < faults:
            half = [(tk[:len(tk) // 2], lb[:len(lb) // 2]) for tk, lb in full]
            for kind, num, batches in (("control", ref.FP8, full),
                                       ("half_batch", ref.FP32, half)):
                out_ = ref.train(sizes, cell.optimizer, num, key, batches)
                row[kind] = check.gaps(out_, fp32)
                row["losses"][kind] = out_["losses"]
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
        rows.append(row)
    return rows


def summary(rows):
    """Per number: the lower reading (largest over the program's seeds) and
    the upper ones (smallest over the control's and the fault's)."""
    from bench.check import NAMES
    out = {}
    for name in NAMES:
        out[name] = {"program_max": max(r["program"][name] for r in rows)}
        for kind in ("control", "half_batch"):
            vals = [r[kind][name] for r in rows if kind in r]
            if vals:
                out[name][f"{kind}_min"] = min(vals)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    from bench.harness import Cell
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: no TPU found")
    cell = Cell.find(args.workload)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") if args.out else open(os.devnull, "w") as out:
        rows = calibrate(cell, args.seeds, args.faults, out)
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "summary": summary(rows),
                      "seconds": time.time() - T0}), flush=True)


if __name__ == "__main__":
    main()
