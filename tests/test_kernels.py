"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes, plus gradient checks for the blocked VJP."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6 import wkv6_pallas


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Flash attention kernel sweeps
# ---------------------------------------------------------------------------

ATTN_SHAPES = [
    # B, Hq, Hkv, Sq, Sk, D
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 96, 96, 64),      # GQA, non-multiple-of-block seq
    (1, 8, 1, 128, 128, 32),    # MQA
    (2, 3, 3, 160, 160, 16),    # odd heads
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
def test_flash_attention_vs_oracle(shape, dtype, causal, window):
    B, Hq, Hkv, Sq, Sk, D = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Hq, Sq, D), dtype)
    k = rand(ks[1], (B, Hkv, Sk, D), dtype)
    v = rand(ks[2], (B, Hkv, Sk, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block=32, interpret=True)
    want = ref.mha_naive(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_q_offset_decodes_prefill_chunk():
    """q_offset positions a later query chunk against the full key prefix."""
    B, H, S, D = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (B, H, S, D))
    k = rand(ks[1], (B, H, S, D))
    v = rand(ks[2], (B, H, S, D))
    full = ref.mha_naive(q, k, v, causal=True)
    half = flash_attention(q[:, :, 64:], k, v, causal=True, q_offset=64,
                           block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(half), np.asarray(full[:, :, 64:]),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(1, 3), st.integers(1, 4), st.sampled_from([32, 48, 96]),
       st.sampled_from([16, 32]))
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(b, h, s, d):
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + h * 10 + s + d), 3)
    q = rand(ks[0], (b, h, s, d))
    k = rand(ks[1], (b, h, s, d))
    v = rand(ks[2], (b, h, s, d))
    got = flash_attention(q, k, v, causal=True, block=16, interpret=True)
    want = ref.mha_naive(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


FLASH_BF16_CASES = [
    # B, Hq, Hkv, Sq, Sk, D, causal, window, q_offset, BLOCK
    (2, 15, 5, 96, 96, 64, True, 0, 0, 32),     # SmolLM's GQA 3:1, 3 blocks
    (1, 6, 2, 100, 100, 64, True, 0, 0, 32),    # S not a multiple of BLOCK
    (1, 3, 1, 160, 160, 64, True, 40, 0, 32),   # window skips blocks
    (1, 3, 1, 64, 160, 64, True, 0, 96, 32),    # q_offset chunk, whole blocks
    (1, 3, 1, 40, 120, 64, True, 24, 80, 16),   # chunk off the block grid
    (1, 2, 2, 72, 72, 128, False, 0, 0, 32),    # non-causal, padded, D=128
]


@pytest.mark.parametrize("case", FLASH_BF16_CASES)
def test_flash_attention_bf16_blocks_vs_oracle(case, monkeypatch):
    """bf16 inputs through the default block rule (``BLOCK`` made small so
    the sequence spans several blocks): single-pass products, held to the
    naive oracle at bf16's precision."""
    from repro.kernels import flash_attention as fa
    B, Hq, Hkv, Sq, Sk, D, causal, window, q_offset, block = case
    monkeypatch.setattr(fa, "BLOCK", block)
    ks = jax.random.split(jax.random.PRNGKey(Sq + Sk + D), 3)
    q = rand(ks[0], (B, Hq, Sq, D), jnp.bfloat16)
    k = rand(ks[1], (B, Hkv, Sk, D), jnp.bfloat16)
    v = rand(ks[2], (B, Hkv, Sk, D), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, interpret=True)
    want = ref.mha_naive(q, k, v, causal=causal, window=window,
                         q_offset=q_offset)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr, those of nested jaxprs included."""
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dots(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_attention_products_take_the_inputs_dtype(dtype):
    """Both products of the kernel take their operands in the inputs' dtype
    and accumulate in f32: single-pass MXU products for bf16 inputs, f32
    products kept for f32 inputs."""
    q = jnp.zeros((1, 3, 64, 64), dtype)
    kv = jnp.zeros((1, 1, 64, 64), dtype)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, block=32, interpret=True))(q, kv, kv).jaxpr
    dots = list(_dots(jaxpr))
    assert dots
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [dtype, dtype]
        assert e.params["preferred_element_type"] == jnp.float32


TILING_CASES = [
    # causal, window, q_offset, sq, sk, b
    (True, 0, 0, 100, 100, 16),
    (False, 0, 0, 40, 72, 16),
    (True, 24, 0, 96, 96, 16),
    (False, 40, 0, 64, 64, 16),
    (True, 0, 48, 32, 80, 16),
    (True, 20, 37, 45, 82, 16),
    (True, 0, 0, 80, 40, 16),       # more queries than keys
]


@pytest.mark.parametrize("case", TILING_CASES)
def test_tiling_matches_the_mask(case):
    """Every (q block, k block) pair: ``sees`` where some score is visible,
    ``full`` where all are, the clamped k range holds every visible pair,
    and ``mask`` hides exactly the scores the oracle's mask hides."""
    from repro.kernels.tiling import Tiling
    causal, window, q_offset, sq, sk, b = case
    nq, nk = -(-sq // b), -(-sk // b)
    t = Tiling(causal=causal, window=window, sk=sk, b=b, nq=nq, nk=nk,
               q_offset=q_offset)
    want = np.zeros((nq * b, nk * b), bool)
    want[:sq, :sk] = np.asarray(ref.attention_mask(
        sq, sk, causal=causal, window=window, q_offset=q_offset))
    ones = jnp.zeros((b, b), jnp.float32)
    for qi in range(nq):
        lo, hi = (int(x) for x in t.k_range(jnp.int32(qi)))
        for ki in range(nk):
            pair = want[qi * b:(qi + 1) * b, ki * b:(ki + 1) * b]
            real = pair[:min(b, sq - qi * b)]      # padded queries: any
            qi_, ki_ = jnp.int32(qi), jnp.int32(ki)
            assert bool(t.sees(qi_, ki_)) == real.any() == (lo <= ki <= hi)
            if real.any():
                assert bool(t.full(qi_, ki_)) == real.all()
                shown = np.isfinite(np.asarray(t.mask(ones, qi_, ki_))).T
                np.testing.assert_array_equal(shown[:len(real)], real)
            if t.nq == t.nk and not q_offset:
                q_lo, q_hi = (int(x) for x in t.q_range(ki_))
                assert (q_lo <= qi <= q_hi) == real.any()


# ---------------------------------------------------------------------------
# Blocked-jnp attention: custom VJP correctness (the XLA fallback path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(causal=False), dict(causal=True, window=24),
    dict(causal=True, kv_len=40),
])
def test_blocked_attention_grads_match_naive(kw):
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v = (rand(ks[i], (B, Hq if i == 0 else Hkv, S, D))
               for i in range(3))
    g = rand(ks[3], (B, Hq, S, D))

    def naive(q, k, v):
        kv_len = kw.get("kv_len")
        out = ref.mha_naive(q, k, v, causal=kw.get("causal", True),
                            window=kw.get("window", 0) or 0)
        if kv_len is not None:
            out = ref.mha_naive(
                q, k[:, :, :kv_len], v[:, :, :kv_len],
                causal=kw.get("causal", True), window=0)
        return out

    f_b = lambda *a: (ref.mha_blocked(*a, block_k=16, **kw)
                      .astype(jnp.float32) * g).sum()
    f_n = lambda *a: (naive(*a).astype(jnp.float32) * g).sum()
    gb = jax.grad(f_b, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_n, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_blocked_attention_traced_mask_params():
    """window/causal as traced scalars (mixed per-layer layouts)."""
    B, H, S, D = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (rand(ks[i], (B, H, S, D)) for i in range(3))

    @jax.jit
    def f(w):
        return ref.mha_blocked(q, k, v, causal=True, window=w, block_k=16)

    np.testing.assert_allclose(
        np.asarray(f(jnp.asarray(24))),
        np.asarray(ref.mha_naive(q, k, v, causal=True, window=24)),
        rtol=2e-5, atol=2e-5)
    # window = S  => equals unwindowed
    np.testing.assert_allclose(
        np.asarray(f(jnp.asarray(S))),
        np.asarray(ref.mha_naive(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas attention backward (ops.attention's VJP on the Pallas path)
# ---------------------------------------------------------------------------

ATTN_BWD_CASES = [
    # B, Hq, Hkv, S, D, causal, window, dtype, block
    (1, 2, 2, 64, 64, True, 0, jnp.float32, None),
    (2, 3, 1, 96, 64, True, 0, jnp.bfloat16, 32),       # GQA 3, many blocks
    (1, 6, 2, 100, 64, True, 0, jnp.float32, 32),       # S needs padding
    (1, 3, 3, 128, 128, False, 0, jnp.float32, 64),
    (1, 3, 1, 150, 128, False, 0, jnp.bfloat16, None),  # one padded block
    (1, 2, 2, 256, 64, True, 40, jnp.float32, 32),      # window skips blocks
    (1, 3, 1, 120, 64, True, 24, jnp.bfloat16, 32),
    (1, 6, 2, 96, 128, False, 48, jnp.float32, 32),
]


def _grads(attend, q, k, v, g):
    def loss(q_, k_, v_):
        return jnp.sum(attend(q_, k_, v_).astype(jnp.float32) * g)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", ATTN_BWD_CASES)
def test_pallas_attention_backward_vs_reference(case, monkeypatch):
    """The Pallas backward behind ``ops.attention`` (interpret mode off the
    TPU) against the VJPs of the naive and the blocked reference.  Its
    products take bf16 operands, so it is held to bf16's precision: a
    hidden score let through, or a visible one dropped, is off by O(1)."""
    from repro.kernels import attention_bwd, ops
    B, Hq, Hkv, S, D, causal, window, dtype, block = case
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    if block:
        monkeypatch.setattr(attention_bwd, "BLOCK", block)
    ks = jax.random.split(jax.random.PRNGKey(S + D), 4)
    q = rand(ks[0], (B, Hq, S, D), dtype)
    k = rand(ks[1], (B, Hkv, S, D), dtype)
    v = rand(ks[2], (B, Hkv, S, D), dtype)
    g = rand(ks[3], (B, Hq, S, D))
    got = _grads(lambda *a: ops.attention(*a, causal=causal, window=window),
                 q, k, v, g)
    for oracle in (ref.mha_naive, ref.mha_blocked):
        want = _grads(lambda *a: oracle(*a, causal=causal, window=window),
                      q, k, v, g)
        for a, b in zip(got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-2 * np.abs(b).max())


@pytest.mark.parametrize("case", ["q_offset", "dq_over_vmem"])
def test_attention_fallback_takes_the_reference_vjp(case, monkeypatch):
    """Shapes the Pallas backward does not take keep the blocked
    reference's VJP, and the Pallas backward is never called: a query chunk
    at ``q_offset`` (chunked prefill), and a query group whose dq would
    not fit its VMEM block."""
    from repro.kernels import attention_bwd, ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)

    def refuse(*a, **kw):
        raise AssertionError(f"the Pallas backward ran ({case})")
    monkeypatch.setattr(attention_bwd, "attention_bwd", refuse)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    if case == "q_offset":
        sq, offset = 32, 64
    else:
        sq, offset = 96, 0
        monkeypatch.setattr(attention_bwd, "DQ_VMEM", 96 * 32 * 4 - 1)
    q = rand(ks[0], (1, 2, sq, 32))
    k, v = rand(ks[1], (1, 2, 96, 32)), rand(ks[2], (1, 2, 96, 32))
    g = rand(ks[3], (1, 2, sq, 32))
    got = _grads(lambda *a: ops.attention(*a, causal=True, q_offset=offset),
                 q, k, v, g)
    want = _grads(lambda *a: ref.mha_blocked(*a, causal=True, q_offset=offset),
                  q, k, v, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV-6 WKV kernel
# ---------------------------------------------------------------------------

WKV_SHAPES = [
    # B, H, T, K, V, chunk
    (1, 1, 64, 8, 8, 16),
    (2, 3, 128, 16, 16, 32),
    (1, 2, 96, 32, 32, 32),
]


@pytest.mark.parametrize("shape", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_pallas_vs_oracle(shape, dtype):
    B, H, T, K, V, C = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r = rand(ks[0], (B, H, T, K), dtype, 0.5)
    k = rand(ks[1], (B, H, T, K), dtype, 0.5)
    v = rand(ks[2], (B, H, T, V), dtype, 0.5)
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, H, T, K), jnp.float32, 0.5))).astype(dtype)
    u = rand(ks[4], (H, K), jnp.float32, 0.5)
    s0 = rand(ks[5], (B, H, K, V), jnp.float32, 0.3)
    got_o, got_s = wkv6_pallas(r, k, v, w, u, s0, chunk=C, interpret=True)
    want_o, want_s = ref.wkv6(r, k, v, w, u, s0)
    tol = 5e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got_o, np.float32),
                               np.asarray(want_o, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=tol, atol=tol)


def test_wkv6_chunked_ref_matches_sequential():
    B, H, T, K, V = 2, 2, 128, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r, k = rand(ks[0], (B, H, T, K), scale=0.5), rand(ks[1], (B, H, T, K), scale=0.5)
    v = rand(ks[2], (B, H, T, V), scale=0.5)
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, H, T, K), scale=0.5)))
    u = rand(ks[4], (H, K), scale=0.5)
    o1, s1 = ref.wkv6(r, k, v, w, u)
    o2, s2 = ref.wkv6_chunked(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4, atol=2e-4)


def test_wkv6_state_chaining():
    """Processing [0:T/2] then [T/2:T] with carried state == full pass."""
    B, H, T, K, V = 1, 2, 64, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k = rand(ks[0], (B, H, T, K), scale=0.5), rand(ks[1], (B, H, T, K), scale=0.5)
    v = rand(ks[2], (B, H, T, V), scale=0.5)
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, H, T, K), scale=0.5)))
    u = rand(ks[4], (H, K), scale=0.5)
    o_full, s_full = ref.wkv6(r, k, v, w, u)
    h = T // 2
    o1, s1 = wkv6_pallas(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h],
                         u, chunk=16, interpret=True)
    o2, s2 = wkv6_pallas(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:],
                         u, s1, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(o_full), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# decode_attend + LSE combine (sequence-sharded long-context decode)
# ---------------------------------------------------------------------------

def test_decode_attend_matches_full_softmax():
    B, H, S, D = 2, 3, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (B, H, 1, D))
    kc = rand(ks[1], (B, H, S, D))
    vc = rand(ks[2], (B, H, S, D))
    ln = jnp.full((B,), S, jnp.int32)
    out, _ = ref.decode_attend(q, kc, vc, ln)
    want = ref.mha_naive(q, kc, vc, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_lse_combine_equals_unsharded():
    """Partial (num, max, den) triples over sequence shards combine exactly."""
    B, H, S, D = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = rand(ks[0], (B, H, 1, D))
    kc = rand(ks[1], (B, H, S, D))
    vc = rand(ks[2], (B, H, S, D))
    ln = jnp.full((B,), S, jnp.int32)
    full, _ = ref.decode_attend(q, kc, vc, ln)
    parts = []
    for sh in range(4):
        ksh = kc[:, :, sh * 16:(sh + 1) * 16]
        vsh = vc[:, :, sh * 16:(sh + 1) * 16]
        _, part = ref.decode_attend(q, ksh, vsh, jnp.full((B,), 16, jnp.int32))
        parts.append(part)
    combined = ref.lse_combine(parts)
    np.testing.assert_allclose(np.asarray(combined, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_rmsnorm():
    x = rand(jax.random.PRNGKey(6), (4, 32), jnp.bfloat16)
    s = jnp.ones((32,), jnp.bfloat16) * 2
    got = ref.rmsnorm(x, s)
    x32 = np.asarray(x, np.float32)
    want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6) * 2
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Fused RMSNorm kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 7, 64), (130, 96), (1, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_vs_oracle(shape, dtype):
    from repro.kernels.rmsnorm import rmsnorm_pallas
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 2).astype(dtype)
    s = (jax.random.normal(jax.random.PRNGKey(1), shape[-1:]) + 1).astype(dtype)
    got = rmsnorm_pallas(x, s, block_rows=32, interpret=True)
    want = ref.rmsnorm(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_pallas_path_grad_matches_reference(monkeypatch):
    """``ops.rmsnorm`` on the Pallas path (interpret mode off the TPU) is
    differentiable and its gradient is the reference's."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 40, 96)) * 2
    s = jax.random.normal(jax.random.PRNGKey(3), (96,)) + 1
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 40, 96))

    def loss(norm):
        return lambda x_, s_: jnp.sum(norm(x_, s_) * w)

    got = jax.grad(loss(ops.rmsnorm), argnums=(0, 1))(x, s)
    want = jax.grad(loss(ref.rmsnorm), argnums=(0, 1))(x, s)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


PER_DEVICE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P
from repro.kernels import ops, ref
ops._use_pallas = lambda: True            # interpret mode off the TPU
mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 2, 1),
            ("pod", "data", "pipe", "tp"), axis_types=(AxisType.Auto,) * 4)
ks = jax.random.split(jax.random.PRNGKey(0), 4)
q = jax.random.normal(ks[0], (2, 4, 4, 32, 16))      # [pipe, B, H, S, D]
kv = jax.random.normal(ks[1], (2, 4, 2, 32, 16))
x = jax.random.normal(ks[2], (2, 4, 32, 64))
s = jax.random.normal(ks[3], (64,)) + 1

def stage(q, kv, x, s):                  # one pipe rank, batch over data
    a = ops.attention(q[0], kv[0], kv[0], causal=True)
    return a[None], ops.rmsnorm(x[0], s)[None]

with jax.set_mesh(mesh):
    a, n = jax.jit(jax.shard_map(
        stage, in_specs=(P("pipe"), P("pipe"), P("pipe"), P()),
        out_specs=(P("pipe"), P("pipe")), axis_names={"pipe"},
        check_vma=False))(q, kv, x, s)
for r in range(2):
    np.testing.assert_allclose(
        np.asarray(a[r]), np.asarray(ref.mha_naive(q[r], kv[r], kv[r],
                                                   causal=True)),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(n[r]),
                               np.asarray(ref.rmsnorm(x[r], s)),
                               rtol=1e-5, atol=1e-5)
print("PER DEVICE OK")
"""


def test_pallas_kernels_per_device_inside_pipeline_shard_map():
    """Under a (data=2, pipe=2) mesh, inside the pipeline's shard_map, the
    Pallas path runs each kernel on its own batch shard and matches the
    reference."""
    from conftest import run_subprocess
    assert "PER DEVICE OK" in run_subprocess(PER_DEVICE, n_devices=4,
                                             timeout=600)
