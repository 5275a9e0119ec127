"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers and compiles for a *described* v5e chip
(``jax.experimental.topologies``), which catches what interpret mode cannot
see — block shapes the TPU tiling rejects, VMEM overuse, a kernel without a
differentiation rule.  The topology is described inside a fixture, never at
import, so only the test worker that runs this file loads the TPU compiler.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.rwkv6 import wkv6_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import trace as trace_lib  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def pallas_path(monkeypatch):
    """Route ``ops`` through the compiled (not interpreted) Pallas kernels."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "hq,hkv,s,d", [(15, 5, 2048, 64), (32, 32, 2048, 128), (15, 5, 4096, 64)],
    ids=["smollm-360m", "deepseek-7b", "smollm-360m-4k"])
def test_flash_attention_fwd_and_vjp_compile(one_chip, pallas_path, hq, hkv,
                                             s, d):
    """Attention: smollm-360m's 15 query / 5 kv heads of 64 at S=2048 and
    4096 (the benchmark's cells), and deepseek-7b's 32 heads of 128 at
    S=2048.  The grad holds the Pallas backward
    kernels, and the benchmark's ``flash_attention`` finds only the forward:
    one array out, q/k/v as operands 0-2 (``flash_attn_fwd_roofline``)."""
    def loss(q, k, v):   # squared, so the backward needs the forward's output
        o = ops.attention(q, k, v, causal=True).astype(jnp.float32)
        return (o * o).sum()

    q, kv = (1, hq, s, d), (1, hkv, s, d)
    fwd = _compile(lambda q, k, v: ops.attention(q, k, v, causal=True),
                   q, kv, kv, sharding=one_chip)
    assert "tpu_custom_call" in fwd.as_text()
    bwd = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                   sharding=one_chip)
    kernels = trace_lib.pallas_kernels(bwd.as_text())
    names = {n: k.functions for n, k in kernels.items()}
    for kernel in ("attention_bwd_stats", "attention_bwd_grads"):
        assert [n for n, f in names.items() if kernel in f], names
    flash = [k for k in kernels.values() if "flash_attention" in k.functions]
    assert flash, names
    for k in flash:
        assert k.result is not None and k.result.shape == (hq, s, d)
        assert [a.shape for a in k.operands[:3]] == [
            (hq, s, d), (hkv, s, d), (hkv, s, d)]
        assert not {"attention_bwd_stats", "attention_bwd_grads"} & set(k.functions)


def test_flash_attention_is_found_in_a_rematerialised_layer_loop(
        one_chip, pallas_path):
    """The train step's form: attention in a ``lax.scan`` of
    ``jax.checkpoint``-ed layers.  There the kernel body's operations can
    lose their own source locations, so the forward is known by the name
    of its ``pallas_call``: ``flash_attention``, on the forward kernel
    alone (``flash_attn_fwd_roofline``)."""
    def loss(q, k, v):
        def layer(x, _):
            return jax.checkpoint(
                lambda x: ops.attention(x, k, v, causal=True))(x), None
        o, _ = jax.lax.scan(layer, q, None, length=2)
        o = o.astype(jnp.float32)
        return (o * o).sum()

    q, kv = (1, 15, 2048, 64), (1, 5, 2048, 64)
    grad = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                    sharding=one_chip)
    kernels = trace_lib.pallas_kernels(grad.as_text())
    flash = [k for k in kernels.values() if "flash_attention" in k.functions]
    assert flash, {n: k.functions for n, k in kernels.items()}
    for k in flash:
        assert k.result is not None and k.result.shape == (15, 2048, 64)
        assert [a.shape for a in k.operands[:3]] == [q[1:], kv[1:], kv[1:]]


def test_rmsnorm_fwd_and_grad_compile(one_chip, pallas_path):
    """smollm-360m norm width 960 over one micro-batch of 2 x 2048 tokens."""
    def loss(x, s):
        y = ops.rmsnorm(x, s).astype(jnp.float32)
        return (y * y).sum()

    x, s = (2, 2048, 960), (960,)
    fwd = _compile(ops.rmsnorm, x, s, sharding=one_chip)
    assert "tpu_custom_call" in fwd.as_text()
    grad = _compile(jax.grad(loss, argnums=(0, 1)), x, s, sharding=one_chip)
    assert "tpu_custom_call" in grad.as_text()


def test_wkv6_compile(one_chip):
    """rwkv6-1.6b WKV: 32 heads, K = V = 64, chunked over T = 1024."""
    B, H, T, K = 1, 32, 1024, 64
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((B, H, T, K), bf), ((B, H, T, K), bf), ((B, H, T, K), bf),
        ((B, H, T, K), bf), ((H, K), jnp.float32),
        ((B, H, K, K), jnp.float32)]]
    compiled = jax.jit(wkv6_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
