"""Record the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py <out-dir>

On a TPU: compiles causal attention and an RMSNorm through the program's
``kernels/ops.py`` (its Pallas kernels), runs them three times inside the
harness's ``bench.window`` and ``bench.step`` spans under the profiler,
and writes ``small.xplane.pb`` (the trace) and ``small.hlo.txt`` (the
compiled module) to ``<out-dir>``.  The committed copies live in
``bench/tests/data/``.
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

Q, KV, X = (1, 15, 512, 64), (1, 5, 512, 64), (1, 512, 960)


def main(out: str):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU found")

    def f(q, k, v, x, s):
        return ops.attention(q, k, v, causal=True), ops.rmsnorm(x, s)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    args = [jax.random.normal(kk, shape, jnp.bfloat16)
            for kk, shape in zip(keys, (Q, KV, KV, X, X[-1:]))]
    step = jax.jit(f)
    compiled = step.lower(*args).compile()
    jax.block_until_ready(step(*args))
    tmp = tempfile.mkdtemp(prefix="bench-small-trace-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = step(*args)
                jax.block_until_ready(y)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out, "small.hlo.txt"), "w") as fh:
        fh.write(compiled.as_text())


if __name__ == "__main__":
    main(sys.argv[1])
