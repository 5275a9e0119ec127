"""Wire engineering (PR 7): codec round-trips, latch invariants, parity.

Host-side property suites cover the codec layer itself (`WireSpec`
parsing/accounting, the `_Codec` encode-at-latch / decode-at-arrival
kernels, the EF-SGD residual algebra) and the plan-level latch invariant
(`plan.assert_route_overlap`: every route arrival has a one-tick-earlier
latch on the producing rank, the property the mpmd double buffering
relies on).  Subprocess tests run the real multi-device executor: every
wire mode must be bitwise-identical across spmd/mpmd, and the lossy
int8-ef mode must pass the single-device oracle to stated tolerances
plus a 5-step loss-curve check.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import run_subprocess

from repro.core import plan as plan_lib
from repro.core import wire as wire_lib
from repro.core.wire import WireSpec


# ---------------------------------------------------------------------------
# WireSpec: parse / round-trip / byte accounting (numpy-only, no devices)
# ---------------------------------------------------------------------------

def test_wirespec_parse_and_roundtrip():
    for s in ("fp32", "bf16", "int8-ef"):
        w = WireSpec.parse(s)
        assert w.chain == w.portal == w.cotangent == s
        assert w.name == s
        assert WireSpec.parse(w.name) == w
    mixed = WireSpec.parse("chain=bf16,portal=fp32,cotangent=int8-ef")
    assert (mixed.chain, mixed.portal, mixed.cotangent) == \
        ("bf16", "fp32", "int8-ef")
    assert WireSpec.parse(mixed.name) == mixed
    assert WireSpec.from_dict(mixed.to_dict()) == mixed
    # parse is idempotent on specs and tolerant of None/empty
    assert WireSpec.parse(mixed) is mixed
    assert WireSpec.parse(None) == wire_lib.WIRE_FP32
    assert WireSpec.parse("") == wire_lib.WIRE_FP32

    assert wire_lib.WIRE_FP32.lossless and not wire_lib.WIRE_FP32.stateful
    assert not mixed.lossless and mixed.stateful
    assert not WireSpec.parse("bf16").lossless
    assert not WireSpec.parse("bf16").stateful

    with pytest.raises(ValueError):
        WireSpec.parse("fp16")
    with pytest.raises(ValueError):
        WireSpec.parse("chain=bf16,carry=fp32")
    with pytest.raises(ValueError):
        WireSpec(block=0)


def test_bytes_factor_and_hop_units():
    assert wire_lib.bytes_factor("fp32") == 1.0
    assert wire_lib.bytes_factor("bf16") == 0.5
    assert wire_lib.bytes_factor("int8-ef", block=256) == \
        pytest.approx(0.25 + 1 / 256)
    # one hop: bytes / bandwidth, normalized to stage-forward units
    u = {c: wire_lib.hop_comm_units(4e6, c, 1e9, 1e-3) for c in
         wire_lib.WIRE_CODECS}
    assert u["fp32"] == pytest.approx(4.0)
    assert u["int8-ef"] < u["bf16"] < u["fp32"]
    # degenerate hardware prices comm at zero instead of dividing by it
    assert wire_lib.hop_comm_units(4e6, "fp32", 0.0, 1e-3) == 0.0


def test_plan_wire_report_prices_classes():
    tplan = plan_lib.plan_for("1f1b", 4, 4, wire="bf16")
    rep = wire_lib.plan_wire_report(tplan, carry_bytes=1000.0)
    assert rep["wire"] == "bf16"
    assert rep["ratio"] == pytest.approx(0.5)
    assert rep["bytes_per_step"] == pytest.approx(
        0.5 * rep["fp32_bytes_per_step"])
    assert rep["bytes_per_tick"] * tplan.n_ticks == pytest.approx(
        rep["bytes_per_step"])
    assert rep["hops"]["chain"] > 0 and rep["hops"]["cotangent_chain"] > 0


# ---------------------------------------------------------------------------
# _Codec kernels: encode at latch, decode at arrival (single host device)
# ---------------------------------------------------------------------------

def _codec(kind, block=256):
    from repro.core.pipeline import _Codec
    return _Codec(kind, block)


@given(st.lists(st.floats(-1e4, 1e4), min_size=1, max_size=300))
@settings(max_examples=30, deadline=None)
def test_bf16_roundtrip_exact_on_representable(vals):
    """bf16 wire is lossless on values already bf16-representable."""
    import jax, jax.numpy as jnp
    x = jnp.asarray(np.array(vals, np.float32))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)   # force representable
    tree = {"h": x, "ids": jnp.arange(x.shape[0], dtype=jnp.int32)}
    c = _codec("bf16")
    wire, ef = c.enc(tree)
    assert ef == ()
    assert wire["h"].dtype == jnp.bfloat16
    out = c.dec(wire, jax.eval_shape(lambda: tree))
    assert np.array_equal(np.asarray(out["h"]), np.asarray(x))
    assert np.array_equal(np.asarray(out["ids"]), np.asarray(tree["ids"]))


@given(st.lists(st.floats(-50, 50), min_size=1, max_size=400),
       st.sampled_from([16, 64, 256]))
@settings(max_examples=30, deadline=None)
def test_int8_ef_residual_bounded_by_block_scale(vals, block):
    """One encode leaves a residual no larger than half a quantization
    step of its block (scale = max|block| / 127) — the shrink the EF
    construction relies on: what is left behind is always sub-step."""
    import jax, jax.numpy as jnp
    x = np.array(vals, np.float32)
    c = _codec("int8-ef", block)
    tree = {"h": jnp.asarray(x)}
    ef0 = c.ef_zeros(jax.eval_shape(lambda: tree))
    wire, ef1 = c.enc(tree, ef0)
    resid = np.asarray(ef1["h"])
    n = x.shape[0]
    pad = (-n) % block
    xb = np.pad(x, (0, pad)).reshape(-1, block)
    rb = np.pad(resid, (0, pad)).reshape(-1, block)
    scale = np.maximum(np.abs(xb).max(axis=1) / 127.0, 1e-12)
    assert (np.abs(rb) <= 0.5 * scale[:, None] + 1e-6).all()
    # and the decode matches x up to exactly that residual
    dec = np.asarray(c.dec(wire, jax.eval_shape(lambda: tree))["h"])
    np.testing.assert_allclose(dec + resid, x, rtol=0, atol=1e-5)


@given(st.lists(st.floats(-10, 10), min_size=1, max_size=200))
@settings(max_examples=20, deadline=None)
def test_int8_ef_telescopes_over_repeated_sends(vals):
    """EF algebra: over k sends of a constant value the decoded payloads
    telescope — sum_t dec_t == k * v - ef_k — so the time-averaged wire
    stream converges on the true value instead of accumulating bias."""
    import jax, jax.numpy as jnp
    v = jnp.asarray(np.array(vals, np.float32))
    tree = {"h": v}
    proto = jax.eval_shape(lambda: tree)
    c = _codec("int8-ef", 64)
    ef = c.ef_zeros(proto)
    total = np.zeros_like(np.asarray(v))
    k = 8
    for _ in range(k):
        wire, ef = c.enc(tree, ef)
        total += np.asarray(c.dec(wire, proto)["h"])
    np.testing.assert_allclose(total, k * np.asarray(v) - np.asarray(ef["h"]),
                               rtol=0, atol=1e-3)
    # single-step quantization error can be ~max|v|/254 per element; the
    # k-averaged stream must beat it (EF pushes the bias to O(1/k))
    assert np.abs(total / k - np.asarray(v)).max() \
        <= np.abs(np.asarray(v)).max() / 254.0 + 1e-3


def test_int8_ef_pred_gates_residual_update():
    """The EF residual only advances when the send predicate is true —
    the property that keeps the EF sequence identical across executors
    (mpmd latches every tick; only real sends may touch the state)."""
    import jax, jax.numpy as jnp
    tree = {"h": jnp.linspace(-3.0, 3.0, 50)}
    proto = jax.eval_shape(lambda: tree)
    c = _codec("int8-ef", 16)
    ef0 = c.ef_zeros(proto)
    _, ef_no = c.enc(tree, ef0, pred=jnp.asarray(False))
    _, ef_yes = c.enc(tree, ef0, pred=jnp.asarray(True))
    assert np.array_equal(np.asarray(ef_no["h"]), np.asarray(ef0["h"]))
    assert not np.array_equal(np.asarray(ef_yes["h"]), np.asarray(ef0["h"]))


def test_codec_nonfloat_and_fp32_identity():
    """fp32 is a strict identity; int leaves ride every codec untouched."""
    import jax, jax.numpy as jnp
    tree = {"tok": jnp.arange(12, dtype=jnp.int32),
            "h": jnp.linspace(-1.0, 1.0, 12)}
    proto = jax.eval_shape(lambda: tree)
    for kind in ("fp32", "bf16", "int8-ef"):
        c = _codec(kind, 8)
        ef = c.ef_zeros(proto)
        wire, _ = c.enc(tree, ef)
        out = c.dec(wire, proto)
        assert np.array_equal(np.asarray(out["tok"]),
                              np.asarray(tree["tok"])), kind
        if kind == "fp32":
            assert wire is tree  # identity, not a copy
    # zeros() builds wire-format registers: int8 leaves carry {q, s}
    z = _codec("int8-ef", 8).zeros(proto)
    assert set(z["h"]) == {"q", "s"} and z["h"]["q"].dtype == jnp.int8
    assert z["tok"].dtype == jnp.int32


# ---------------------------------------------------------------------------
# Latch invariant: every route arrival has a one-tick-earlier latch
# ---------------------------------------------------------------------------

SKIPS = [plan_lib.SkipSpec("s02", 0, (2,)), plan_lib.SkipSpec("s13", 1, (3,))]


@pytest.mark.parametrize("schedule", ["gpipe_tasked", "1f1b",
                                      "interleaved:2", "zb"])
@pytest.mark.parametrize("skips", [(), SKIPS],
                         ids=["chain-only", "portal-skips"])
def test_route_latch_invariant(schedule, skips):
    tplan = plan_lib.plan_for(schedule, 4, 4, skips=skips,
                              residuals="recompute")
    checked = plan_lib.assert_route_overlap(tplan)
    n_real = sum((rt.send >= 0).sum() + (rt.g_send >= 0).sum()
                 for rt in tplan.routes)
    if skips:
        assert tplan.routes and checked > 0
        # arrivals and latches pair up one-to-one (plus relay reads)
        assert checked >= len(tplan.routes)
    else:
        assert checked == n_real or not tplan.routes


def test_route_latch_tripwire_catches_violation():
    """Erasing one latch must trip assert_route_overlap — the tripwire
    actually checks the property, it is not vacuously green."""
    tplan = plan_lib.plan_for("1f1b", 4, 4, skips=SKIPS)
    rt = next(r for r in tplan.routes if r.fwd_perm)
    t, r = map(int, next(zip(*np.nonzero(rt.recv >= 0))))
    src = {d: s for s, d in rt.fwd_perm}.get(r, r)
    saved = rt.send[t - 1, src]
    rt.send[t - 1, src] = -1
    try:
        with pytest.raises(AssertionError):
            plan_lib.assert_route_overlap(tplan)
    finally:
        rt.send[t - 1, src] = saved


# ---------------------------------------------------------------------------
# HardwareSpec: new wire fields parse on both YAML paths
# ---------------------------------------------------------------------------

HW_TEXT = """\
name: test-slice
ranks: 2
memory_bytes: 1.0e9      # 1 GB
flops: 1.0e12
ici_bytes_per_s: 1.0e10
link_bandwidth_bytes_per_s: 2.5e9
wire: chain=bf16,portal=fp32,cotangent=int8-ef
"""


def test_hardware_spec_wire_fields(tmp_path):
    from repro.planner.hardware import HardwareSpec, _parse_flat_yaml
    p = tmp_path / "hw.yaml"
    p.write_text(HW_TEXT)
    hw = HardwareSpec.from_yaml(str(p))
    assert hw.link_bandwidth_bytes_per_s == 2.5e9
    assert hw.link_bw == 2.5e9
    assert WireSpec.parse(hw.wire).chain == "bf16"
    # the flat no-PyYAML fallback parses the same schema
    flat = _parse_flat_yaml(HW_TEXT)
    assert HardwareSpec.from_dict(flat) == hw
    # 0 sentinel falls back to the ICI figure
    assert hw.with_(link_bandwidth_bytes_per_s=0.0).link_bw == 1.0e10
    with pytest.raises(ValueError):
        hw.with_(wire="fp64")
    with pytest.raises(ValueError):
        hw.with_(link_bandwidth_bytes_per_s=-1.0)


# ---------------------------------------------------------------------------
# EFCompressor regression: pytrees containing tuples (satellite fix)
# ---------------------------------------------------------------------------

def test_ef_compressor_tuple_pytree_roundtrip():
    """compress_reduce must treat tuples as structure, not leaves — the
    old unflatten special-cased `isinstance(x, tuple)` and corrupted
    grads whose pytree contains tuple nodes."""
    import jax, jax.numpy as jnp
    from repro.runtime.compression import EFCompressor
    k = jax.random.PRNGKey(0)
    g = {"attn": (jax.random.normal(k, (33,)),
                  jax.random.normal(jax.random.fold_in(k, 1), (4, 5))),
         "mlp": (jax.random.normal(jax.random.fold_in(k, 2), (7,)),)}
    comp = EFCompressor(block=16)
    ef = comp.init_state(g)
    out, ef2 = comp.compress_reduce(g, ef)
    assert jax.tree_util.tree_structure(out) == \
        jax.tree_util.tree_structure(g)
    assert jax.tree_util.tree_structure(ef2) == \
        jax.tree_util.tree_structure(g)
    # dequantized + residual reconstructs every leaf exactly, leaf-aligned
    # with the ORIGINAL tree (the old tuple special-case mis-split here)
    for ga, oa, ea in zip(jax.tree_util.tree_leaves(g),
                          jax.tree_util.tree_leaves(out),
                          jax.tree_util.tree_leaves(ef2)):
        assert oa.shape == ga.shape
        np.testing.assert_allclose(np.asarray(oa) + np.asarray(ea),
                                   np.asarray(ga), rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# Multi-device executor: wire modes bitwise across spmd/mpmd; int8-ef
# passes the single-device oracle + 5-step loss-curve check
# ---------------------------------------------------------------------------

WIRE_PARITY = """
import zlib
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.core.pipeline import pipeline_grad_call, microbatch, unmicrobatch

key = jax.random.PRNGKey(0)
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")

def lm_lg(schedule, pipe, m, executor, wire="fp32"):
    # whisper-tiny: encoder-decoder portals, so the route latch path and
    # the portal/cotangent codec classes are all exercised
    arch = configs.smoke_arch("whisper-tiny")
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m,
                          remat="full", schedule=schedule,
                          residuals="recompute", executor=executor,
                          wire=wire)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = {}
    for k, v in model.input_specs(shape).items():
        kk = jax.random.fold_in(key, zlib.crc32(k.encode()) % 1000)
        batch[k] = (jax.random.randint(kk, v.shape, 0, arch.vocab)
                    if v.dtype == jnp.int32
                    else jax.random.normal(kk, v.shape, v.dtype) * 0.1)
    mbg = shape.global_batch // m
    cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
    with set_mesh(mesh):
        pg, _ = pipeline_grad_call(
            model.make_stage_apply(model.consts()), mesh=mesh, cfg=pcfg,
            loss_fn=lambda hp, c, la: model.head_loss(hp, c["h"],
                                                      la["labels"]),
            skips=model.skips(), skip_protos=model.skip_protos(mbg, 16),
            carry_proto=cp)
        @jax.jit
        def fused(p, b):
            fresh, evjp = jax.vjp(
                lambda e: model.embed_inputs(e, b), p["embed"])
            head_ps = {"head": p["head"], "embed": p["embed"]}
            loss, gs, gh, ig = pg(p["stages"], head_ps, microbatch(fresh, m),
                                  microbatch({"labels": b["labels"]}, m))
            (ge,) = evjp(unmicrobatch(ig))
            ge = jax.tree.map(jnp.add, ge, gh["embed"])
            return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
        loss, grads = fused(params, batch)
    return np.asarray(loss), jax.tree.map(np.asarray, grads)

def gflat(g):
    return np.concatenate([np.ravel(l) for l in jax.tree.leaves(g)])

base = lm_lg("1f1b", 2, 4, "spmd")
for wire in ("fp32", "bf16", "int8-ef",
             "chain=fp32,portal=int8-ef,cotangent=bf16"):
    s = lm_lg("1f1b", 2, 4, "spmd", wire=wire)
    m_ = lm_lg("1f1b", 2, 4, "mpmd", wire=wire)
    # the core contract survives the codec: spmd == mpmd BITWISE in loss
    # and grads for every wire mode (EF updates are send-predicated)
    assert np.array_equal(s[0], m_[0]), (wire, s[0], m_[0])
    assert np.array_equal(gflat(s[1]), gflat(m_[1])), wire
    if wire == "fp32":
        # lossless mode: bitwise against the unwired baseline semantics
        assert np.array_equal(s[0], base[0])
        assert np.array_equal(gflat(s[1]), gflat(base[1]))
    else:
        rel = abs(float(s[0]) - float(base[0])) / abs(float(base[0]))
        assert rel < 0.05, (wire, rel)
    print("wire parity OK", wire)
print("WIRE PARITY OK")
"""

INT8_ORACLE = """
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.core.pipeline import (TickCtx, pipeline_grad_call, microbatch,
                                 unmicrobatch)
from repro.optim import optimizers as optim

arch = configs.smoke_arch("smollm-360m")
key = jax.random.PRNGKey(0)
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")
m = 4
batch_of = lambda model: {
    k: jax.random.randint(jax.random.fold_in(key, len(k)), v.shape, 0,
                          arch.vocab)
    for k, v in model.input_specs(shape).items()}

def curve(wire, executor):
    pcfg = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=m,
                          schedule="1f1b", executor=executor, wire=wire)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = batch_of(model)
    ocfg = optim.OptimizerConfig(lr=2e-3, warmup_steps=2, total_steps=20)
    with set_mesh(mesh):
        step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape,
                                              ocfg))
        p, o = params, optim.init(ocfg, params)
        ls = []
        for _ in range(5):
            p, o, metrics = step(p, o, batch)
            ls.append(float(metrics["loss"]))
    return model, params, batch, ls

# 5-step loss-curve check: the int8-ef wire must track the lossless
# curve within 5% at every step and still make training progress
model, params, batch, base = curve("fp32", "mpmd")
_, _, _, lossy = curve("int8-ef", "mpmd")
print("fp32   :", base)
print("int8-ef:", lossy)
np.testing.assert_allclose(lossy, base, rtol=5e-2)
assert lossy[-1] < lossy[0]

# single-shot grads vs a from-scratch single-device jax.grad oracle, to
# the stated int8-ef tolerances (one quantized hop per boundary; the EF
# state is cold on step one, so the error is pure quantization noise)
stage_apply = model.make_stage_apply(model.consts())
def oracle_loss(p, b):
    fresh = model.embed_inputs(p["embed"], b)
    fresh_mb = jax.tree.map(
        lambda a: a.reshape((m, a.shape[0] // m) + a.shape[1:]), fresh)
    labels_mb = b["labels"].reshape(
        (m, b["labels"].shape[0] // m) + b["labels"].shape[1:])
    hp = {"head": p["head"], "embed": p["embed"]}
    total = jnp.zeros((), jnp.float32)
    for i in range(m):
        fresh_i = jax.tree.map(lambda a: a[i], fresh_mb)
        carry = {"h": jnp.zeros_like(fresh_i["h"])}
        for s in range(model.n_stages):
            ctx = TickCtx(stage=jnp.int32(s), micro=jnp.int32(i),
                          valid=jnp.asarray(True), t=jnp.int32(0),
                          fresh=fresh_i, n_stages=model.n_stages, n_micro=m)
            p_s = jax.tree.map(lambda a: a[s], p["stages"])
            carry, _, _ = stage_apply(p_s, carry, {}, {}, ctx)
        total = total + model.head_loss(hp, carry["h"],
                                        labels_mb[i]).astype(jnp.float32)
    return total / m

o_loss, o_grads = jax.jit(jax.value_and_grad(oracle_loss))(params, batch)
pcfg = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=m,
                      schedule="1f1b", executor="mpmd", wire="int8-ef")
mesh = mesh_lib.make_smoke_mesh(pcfg)
mbg = shape.global_batch // m
cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
with set_mesh(mesh):
    pg, _ = pipeline_grad_call(
        stage_apply, mesh=mesh, cfg=pcfg,
        loss_fn=lambda hp, c, la: model.head_loss(hp, c["h"], la["labels"]),
        skips=model.skips(), skip_protos=model.skip_protos(mbg, 16),
        carry_proto=cp)
    @jax.jit
    def fused(p, b):
        fresh, evjp = jax.vjp(lambda e: model.embed_inputs(e, b), p["embed"])
        hp = {"head": p["head"], "embed": p["embed"]}
        loss, gs, gh, ig = pg(p["stages"], hp, microbatch(fresh, m),
                              microbatch({"labels": b["labels"]}, m))
        (ge,) = evjp(unmicrobatch(ig))
        ge = jax.tree.map(jnp.add, ge, gh["embed"])
        return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
    loss, grads = fused(params, batch)
np.testing.assert_allclose(float(o_loss), float(loss), rtol=2e-3)
for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(o_grads)[0],
                        jax.tree_util.tree_leaves(grads)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=2e-3,
                               err_msg=f"int8-ef oracle {path}")
print("INT8 ORACLE OK")
"""


def test_wire_executor_parity():
    """Every wire mode is bitwise-identical across spmd/mpmd (loss AND
    grads) on the portal model; fp32 is additionally bitwise against the
    unwired baseline, lossy modes land within 5% of its loss."""
    out = run_subprocess(WIRE_PARITY, n_devices=8, timeout=2400)
    assert "WIRE PARITY OK" in out


def test_wire_int8_oracle_tolerance():
    """int8-ef wire passes the single-device oracle to stated tolerances
    (grads rtol=5e-3/atol=2e-3 — step one ships cold-EF quantization
    noise) and tracks the lossless 5-step loss curve within 5% while
    still training."""
    out = run_subprocess(INT8_ORACLE, n_devices=8, timeout=2400)
    assert "INT8 ORACLE OK" in out
