"""Per-architecture smoke tests (assignment requirement): reduced configs of
the same family, one train step + prefill + decode on CPU, asserting output
shapes and finiteness.  The FULL configs are exercised only via the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import set_mesh
from repro import configs
from repro.configs.base import ShapeConfig
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.optim import optimizers as optim


def make_batch(model, shape, key):
    out = {}
    for k, v in model.input_specs(shape).items():
        kk = jax.random.fold_in(key, len(k))
        if v.dtype == jnp.int32:
            out[k] = jax.random.randint(kk, v.shape, 0, model.arch.vocab)
        else:
            out[k] = (jax.random.normal(kk, v.shape) * 0.1).astype(v.dtype)
    return out


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_arch_smoke_train_and_serve(name):
    arch = configs.smoke_arch(name)
    pcfg = configs.smoke_parallel(name)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    shape = ShapeConfig("smoke", seq_len=16, global_batch=4, kind="train")
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=8)
    opt = optim.init(ocfg, params)
    with set_mesh(mesh):
        step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))
        batch = make_batch(model, shape, key)
        losses = []
        for _ in range(3):
            params, opt, metrics = step(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all(), f"{name}: non-finite loss {losses}"
        assert losses[-1] < losses[0], f"{name}: loss not decreasing {losses}"

        # prefill + one decode step
        pshape = ShapeConfig("p", seq_len=16, global_batch=4, kind="prefill")
        pf = jax.jit(steps.build_prefill_step(model, pcfg, mesh, pshape))
        cache = model.init_cache(pshape, pcfg.n_micro, filled=False)
        pbatch = {k: v for k, v in batch.items() if k != "labels"}
        logits, cache = pf(params, cache, pbatch)
        assert logits.shape == (4, 1, arch.vocab)
        assert bool(jnp.isfinite(logits).all()), f"{name}: prefill NaN"

        dshape = ShapeConfig("d", seq_len=16, global_batch=4, kind="decode")
        sv = jax.jit(steps.build_serve_step(model, pcfg, mesh, dshape))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        logits2, cache = sv(params, cache, tok)
        assert logits2.shape == (4, 1, arch.vocab)
        assert bool(jnp.isfinite(logits2).all()), f"{name}: decode NaN"


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_full_config_matches_assignment(name):
    """The FULL (non-reduced) configs carry the assigned dimensions."""
    a = configs.get_arch(name)
    expect = {
        "whisper-tiny": (4, 384, 1536, 51865),
        "smollm-360m": (32, 960, 2560, 49152),
        "gemma-2b": (18, 2048, 16384, 256000),
        "llama3-405b": (126, 16384, 53248, 128256),
        "deepseek-7b": (30, 4096, 11008, 102400),
        "rwkv6-1.6b": (24, 2048, 7168, 65536),
        "dbrx-132b": (40, 6144, 10752, 100352),
        "mixtral-8x7b": (32, 4096, 14336, 32000),
        "pixtral-12b": (40, 5120, 14336, 131072),
        "hymba-1.5b": (32, 1600, 5504, 32001),
    }[name]
    assert (a.n_layers, a.d_model, a.d_ff, a.vocab) == expect
    pc = configs.get_parallel(name)
    assert pc.pipe * pc.tp == 16, "model axis must factor into pipe x tp"
    if name == "dbrx-132b":
        assert a.moe.n_experts == 16 and a.moe.top_k == 4
    if name == "mixtral-8x7b":
        assert a.moe.n_experts == 8 and a.moe.top_k == 2
        assert a.attn.kind == "swa" and a.attn.window == 4096
    if name == "hymba-1.5b":
        assert a.ssm.state_dim == 16 and a.attn.global_layers
    if name == "gemma-2b":
        assert a.attn.n_kv_heads == 1 and a.attn.head_dim == 256
    if name == "llama3-405b":
        assert a.attn.n_heads == 128 and a.attn.n_kv_heads == 8


def test_param_counts_in_range():
    """Total parameters land near the names on the tin (sanity on configs)."""
    expect = {"smollm-360m": (0.30e9, 0.45e9),
              "gemma-2b": (2.0e9, 3.2e9),
              "llama3-405b": (390e9, 420e9),
              "deepseek-7b": (6e9, 8e9),
              "rwkv6-1.6b": (1.2e9, 2.2e9),
              "mixtral-8x7b": (44e9, 50e9),
              "dbrx-132b": (125e9, 140e9),
              "pixtral-12b": (11e9, 14e9),
              "hymba-1.5b": (0.9e9, 2.0e9)}
    for name, (lo, hi) in expect.items():
        n = configs.get_arch(name).total_params()
        assert lo < n < hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"


def test_optimized_parallel_variants():
    """§Perf-hillclimbed layouts stay legal tilings of the model axis."""
    for name in configs.ARCH_NAMES:
        p = configs.get_parallel(name, optimized=True)
        assert p.pipe * p.tp * p.dp2 == 16
    d = configs.get_parallel("deepseek-7b", optimized=True)
    assert d.gather_weights_once and d.stream_inputs
    w = configs.get_parallel("whisper-tiny", optimized=True)
    assert w.dp2 == 4 and w.pipe == 2
    l3 = configs.get_parallel("llama3-405b", optimized=True)
    assert l3.remat_layers
