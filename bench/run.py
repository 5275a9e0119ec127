"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's trainer through the program's entry-point layout,
makes bf16 weights on the device from the seed, and runs the first steps,
which compile (or load from ``.jax_cache/``) and are read for the check.
The window then runs the trainer's steps back to back for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics read from its trace.  After the window the plain float32
reference follows the first steps and decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``: each compared number beside its limit, which
are also the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import Cell, load_json, run
    cell = Cell.find(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX platform is {devices[0].platform!r})")
    if len(devices) < cell.chips:
        sys.exit(f"bench: {cell.name} needs {cell.chips} chips, "
                 f"{len(devices)} present")
    peaks = load_json("bench", "peaks.json")["devices"].get(devices[0].device_kind)
    if peaks is None:
        sys.exit(f"bench: no peaks for device kind {devices[0].device_kind!r} "
                 "in bench/peaks.json")

    result = run(cell, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t0=T0, peaks=peaks)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
