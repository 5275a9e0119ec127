"""One run of one cell: set-up, the measured window, the check.

Everything particular to a cell is found by name from ``BENCHMARK.json``:
the configuration file (its sizes and the reference module it names,
``bench/configs/<reference>.py``, which also states what the benchmark
must know of the model family), the traffic file
``bench/traffic/<traffic>.json``, the cell's limits
``bench/workloads/<cell>.json`` and, for a traced run, one reader per
per-layer metric, ``bench/metrics/<metric>.py``.  A per-layer metric that
``BENCHMARK.json`` lists for the cell and that reads nothing fails the run.
"""
from __future__ import annotations

import functools
import gc
import importlib
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
REF_STEPS = 3        # steps the program is read at and the reference follows


def load_json(*parts) -> Any:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    optimizer: Dict
    per_layer: List[str]

    @classmethod
    def find(cls, workload: str) -> "Cell":
        spec = load_json("BENCHMARK.json")
        wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
        if wl is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
        metrics = [m["name"] for m in spec["per_layer"]
                   if workload in m.get("workloads", [workload])]
        return cls(workload, int(wl["chips"]), load_json(cfg["file"]),
                   load_json("bench", "traffic", wl["traffic"] + ".json"),
                   load_json("bench", "workloads", workload + ".json")["limits"],
                   load_json("bench", "optimizer.json"), metrics)

    def reference(self):
        """The configuration's reference module."""
        return importlib.import_module(
            f"bench.configs.{self.config['reference']}")


@dataclass
class Reading:
    """What a per-layer metric's reader may read of a traced run."""
    reference: Any          # the configuration's reference module
    sizes: Dict
    traffic: Dict
    chips: int
    peaks: Dict
    steps: int
    window_s: float
    trace: Any
    hlo: str


def seed_key(seed: int) -> np.ndarray:
    """A raw ``uint32[2]`` PRNG key for any whole number."""
    return np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)


class CompileCounter:
    """Counts the compilations (cache loads too) JAX makes."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float,
        peaks: Optional[Dict] = None) -> Dict:
    """Run ``cell`` once; returns the result line's fields, with the
    compared numbers under ``checks``.  ``t0`` is the process's start on
    the ``time.time`` clock."""
    import jax
    import jax.numpy as jnp
    from bench import check, program
    from bench import trace as trace_lib
    from bench.traffic import ZipfRows
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    ref = cell.reference()
    sizes = cell.config["sizes"]
    B, S = int(cell.traffic["global_batch"]), int(cell.traffic["seq_len"])

    # ---------------------------------------------------------------- set-up
    data = ZipfRows(cell.traffic, sizes["vocab_size"], seed)
    tr = program.make_trainer(program.make_arch(cell.config, ref), cell.traffic,
                              cell.optimizer, cell.chips, data)
    key = jnp.asarray(seed_key(seed))
    init = functools.partial(ref.init_weights, c=sizes)
    state, prog = first_steps(tr, init, key, cell.optimizer,
                              ref.PROGRAM_LEAVES)
    compiled = program.compiled_step(tr, state, REF_STEPS)
    peak_hbm = program.peak_bytes(compiled)
    hlo = compiled.as_text() if trace else ""
    del compiled
    jax.block_until_ready(state)
    setup_s = time.time() - t0
    log(f"set-up {setup_s:.3f} s, {compiles.n} compilations or cache loads; "
        f"layout pipe={tr.pcfg.pipe} m={tr.pcfg.n_micro} "
        f"schedule={tr.pcfg.schedule}; first losses {prog['losses']}")

    # ---------------------------------------------------------------- window
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jitted = tr.jit_step

        def dispatch(*args):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                return jitted(*args)
        tr.jit_step = dispatch
        jax.profiler.start_trace(trace_dir)
    before = compiles.n
    steps = failed = 0
    i = REF_STEPS
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                state, m = tr.step(state, i)
                jax.block_until_ready(state)
            i += 1
            steps += 1
            failed += int(m.get("finite", 1.0) == 0.0)
            window_s = time.perf_counter() - start
            if window_s >= seconds:
                break
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.n - before
    devices = list(tr.mesh.devices.flat)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    log(f"window {window_s:.6f} s, {steps} steps, {failed} skipped, "
        f"{window_compiles} compilations inside the window")
    log(f"peak_hbm_gb {peak_hbm / 1e9:.6f} (compiled step: arguments + "
        f"temporaries + outputs - aliased); peak_bytes_in_use {in_use}")
    dev = devices[0]
    result = {"correct": False, "attempted": steps, "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": in_use}}
    if trace:
        t = trace_lib.load(trace_lib.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = t.window()
        busy = [trace_lib.busy_ns(t.devices.get(d.id, []), lo, hi)
                for d in devices]
        result["device"]["busy_s"] = sum(busy) / len(busy) * 1e-9
        result["device"]["window_s"] = (hi - lo) * 1e-9
        ctx = Reading(ref, sizes, cell.traffic, cell.chips, peaks, steps,
                      window_s, t, hlo)
        units = {m["name"]: m["unit"]
                 for m in load_json("BENCHMARK.json")["per_layer"]}
        for name in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{name}")
            value = reader.read(ctx)
            if value is None:
                raise SystemExit(f"bench: {name}, listed for {cell.name} in "
                                 "BENCHMARK.json, found nothing to read in "
                                 "the trace")
            result["metrics"][name] = {"value": value, "unit": units[name]}
        result["breakdown"] = breakdown(t, devices[0].id, hlo)
    else:
        result["metrics"] = {
            "tokens_per_s": {"value": steps * B * S / window_s,
                             "unit": "tokens/s"},
            "peak_hbm_gb": {"value": peak_hbm / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    # ----------------------------------------------------------------- check
    batches = [data.batch_at(i) for i in range(REF_STEPS)]
    del state, tr, m, data
    gc.collect()
    t_ref = time.perf_counter()
    out = ref.train(sizes, cell.optimizer, ref.FP32, key,
                    [(b["tokens"], b["labels"]) for b in batches])
    values = check.gaps(prog, out)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; losses "
        f"{out['losses']} against {prog['losses']}; gaps {values}")
    result["correct"], rows = check.judge(values, cell.limits)
    result["correct"] = result["correct"] and window_compiles == 0 \
        and all(math.isfinite(x) for x in prog["losses"])
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    result["checks"]["window_compilations"] = {"value": window_compiles,
                                               "limit": 0}
    return result


def first_steps(tr, init, key, optimizer: Dict, leaves: Dict):
    """Make the state from ``key`` and run the first ``REF_STEPS`` steps
    through the trainer, reading what the check compares: each step's
    loss, the first gradient's norms (from Adam's first moment after one
    step) and the master weights' change.  ``leaves`` maps the program's
    parameter paths to the reference's names.  Returns ``(state,
    readings)``."""
    from bench import check, program
    state = program.make_state(tr, init, key, leaves)
    losses = []
    for i in range(REF_STEPS):
        state, m = tr.step(state, i)
        losses.append(m["loss"])
        if i == 0:
            grads = check.program_grad_norms(
                program.moment_norms(tr, state["opt"], leaves), m["grad_norm"],
                optimizer)
    change = program.change_norms(tr, state["opt"], init, key, leaves)
    return state, {"losses": losses, "grad_norms": grads,
                   "change_norms": change}


def breakdown(t, device: int, hlo: str) -> Dict[str, List]:
    """The device's ten operations with the most time of their own in the
    window (loops less their bodies; Pallas kernels marked) and its ten
    longest idle gaps."""
    from bench import trace as trace_lib
    kernels = trace_lib.pallas_kernels(hlo)
    lines = trace_lib.instruction_lines(hlo)
    lo, hi = t.window()
    own = trace_lib.self_times(t.devices.get(device, []), lo, hi)

    def label(name):
        if name in kernels:
            k = kernels[name]
            kind = ("flash_attention" if "flash_attention" in k.functions
                    else "rmsnorm" if "rmsnorm" in k.functions else "kernel")
            return f"{name} (Pallas {kind})"
        op = re.search(r'op_name="([^"]*)"', lines.get(name, ""))
        return f"{name} ({op.group(1).rsplit('/', 2)[-1]})" if op else name
    ops = sorted(own.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[label(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in trace_lib.idle_gaps(t, device)]}
