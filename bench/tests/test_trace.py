"""The reduction from trace to metrics.

The kernels are found in a real compiled HLO: the test compiles the
program's Pallas attention and RMSNorm for a described TPU v5e (no chip
needed) and reads the ``tpu_custom_call`` instructions back.  The device
timeline is built by hand, in the form the TPU profiler writes it: each
operation named by its HLO instruction's text, loops around their bodies,
module runs on their own line, the harness's spans on the host.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, trace
from bench.configs import llama
from bench.metrics import device_idle_share, flash_attn_fwd_roofline, mfu
from repro.kernels import ops

PEAKS = harness.load_json("bench", "peaks.json")["devices"]["TPU v5 lite"]
Q, KV, X = (1, 15, 512, 64), (1, 5, 512, 64), (1, 512, 960)


@pytest.fixture(scope="module")
def hlo():
    """HLO of attention + RMSNorm through ``kernels/ops.py`` with the
    Pallas kernels, compiled for one described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    use, interp = ops._use_pallas, ops._interpret
    ops._use_pallas, ops._interpret = (lambda: True), (lambda: False)
    try:
        def f(q, k, v, x, s):
            return ops.attention(q, k, v, causal=True), ops.rmsnorm(x, s)
        args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
                for shape in (Q, KV, KV, X, X[-1:])]
        return jax.jit(f).lower(*args).compile().as_text()
    finally:
        ops._use_pallas, ops._interpret = use, interp


def _kernel_names(hlo):
    ks = trace.pallas_kernels(hlo)
    flash = [n for n, k in ks.items() if "flash_attention" in k.functions]
    norm = [n for n, k in ks.items() if "rmsnorm" in k.functions
            and "flash_attention" not in k.functions]
    return ks, flash, norm


def test_kernels_are_found_by_the_functions_they_were_made_in(hlo):
    ks, flash, norm = _kernel_names(hlo)
    assert len(flash) == 1 and len(norm) == 1
    f, n = ks[flash[0]], ks[norm[0]]
    assert [a.shape for a in f.operands] == [(15, 512, 64), (5, 512, 64), (5, 512, 64)]
    assert f.result.shape == (15, 512, 64)
    assert [a.shape for a in n.operands] == [(512, 960), (960,)]


def test_operations_are_named_by_their_instruction():
    assert trace.instruction_name(
        "%while.391 = (s32[]{:T(128)}, bf16[4,2]{1,0}, /*index=5*/bf16[2]{0}) "
        "while((s32[]{:T(128)}, bf16[4,2]{1,0}) %tuple.1), condition=%c") == "while.391"
    assert trace.instruction_name(
        "%closed_call.77 = bf16[30,2048,64]{2,1,0:T(8,128)(2,1)S(1)} "
        "custom-call(bf16[30,2048,64]{2,1,0} %bitcast.1)") == "closed_call.77"


def test_union_and_self_time():
    ev = [trace.Event("a", 0, 10), trace.Event("b", 5, 10), trace.Event("c", 30, 5)]
    assert trace.intervals(ev, 0, 100) == [(0, 15), (30, 35)]
    assert trace.busy_ns(ev, 8, 32) == 7 + 2
    loop = [trace.Event("while.1", 0, 100), trace.Event("fusion.2", 10, 30),
            trace.Event("fusion.3", 50, 20), trace.Event("copy.4", 200, 5)]
    own = trace.self_times(loop, 0, 1000)
    assert own == pytest.approx({"while.1": 50e-9, "fusion.2": 30e-9,
                                 "fusion.3": 20e-9, "copy.4": 5e-9})


def _timeline(hlo):
    """Two steps in a 10 us window: each step one module run holding a loop,
    a flash call of 800 ns and a norm call of 100 ns; 1 us idle between."""
    ks, flash, norm = _kernel_names(hlo)
    mod = trace.module_name(hlo) + "(8122)"
    devs, mods = [], []
    for start in (1_000, 6_000):
        mods.append(trace.Event(mod, start, 4_000))
        devs += [trace.Event("while.9", start, 4_000),
                 trace.Event(flash[0], start + 100, 800),
                 trace.Event(norm[0], start + 1_000, 100),
                 trace.Event("fusion.5", start + 1_200, 2_800)]
    devs.append(trace.Event(flash[0], 20_000, 800))        # outside the window
    host = [trace.Event("bench.window", 1_000, 10_000)]
    host += [trace.Event("bench.step", s, 5_000) for s in (1_000, 6_000)]
    host += [trace.Event("bench.dispatch", s, 200) for s in (1_000, 6_000)]
    return trace.Trace({0: devs}, sorted(host, key=lambda e: e.start), {0: mods}), ks


def _ctx(t, hlo, steps=2):
    sizes = harness.load_json("bench", "configs", "smollm-360m.json")["sizes"]
    return harness.Reading(llama, sizes, {"global_batch": 8, "seq_len": 2048},
                           1, PEAKS, steps, 10e-6, t, hlo)


def test_flash_roofline_from_shapes_and_kernel_time(hlo):
    t, ks = _timeline(hlo)
    events = trace.kernel_events(t, hlo, "flash_attention")
    assert len(events) == 2                     # the third is outside the window
    flops, _ = flash_attn_fwd_roofline.cost(events[0][1])
    assert flops == 15 * 512 * 513 / 2 * 2 * 128
    want = 100 * 2 * flops / PEAKS["bf16_flops"] / (2 * 800e-9)
    assert flash_attn_fwd_roofline.read(_ctx(t, hlo)) == pytest.approx(want)


def test_idle_share_busy_time_and_gaps(hlo):
    t, _ = _timeline(hlo)
    # busy: 4 us a step, twice, in a 10 us window
    assert device_idle_share.read(_ctx(t, hlo)) == pytest.approx(20.0)
    gaps = trace.idle_gaps(t, 0)
    assert gaps[0] == ("bench.step (metrics read-back)", pytest.approx(1e-6))
    assert sum(s for _, s in gaps) == pytest.approx(2e-6)


def test_breakdown_lists_self_time_and_marks_kernels(hlo):
    t, _ = _timeline(hlo)
    b = harness.breakdown(t, 0, hlo)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "fusion.5"
    assert any("(Pallas flash_attention)" in n for n in names)
    assert any("(Pallas rmsnorm)" in n for n in names)
    own = dict(b["device_ops"])
    assert own["while.9"] == pytest.approx(2 * 300e-9)     # less its body
    assert 0 < len(b["idle_gaps"]) <= 10


def test_a_reader_with_nothing_to_read_returns_none(hlo):
    t, _ = _timeline(hlo)
    empty = harness.Reading(llama, {}, {}, 1, PEAKS, 0, 1.0, trace.Trace({}, t.host, {}), "")
    for reader in (mfu, device_idle_share, flash_attn_fwd_roofline):
        assert reader.read(empty) is None


# ---------------------------------------------------------------------------
# A trace recorded on a TPU v5e by bench/tests/record_trace.py: three steps
# of the Pallas attention and RMSNorm inside the harness's spans
# ---------------------------------------------------------------------------

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(DATA, "small.hlo.txt")) as f:
        hlo = f.read()
    return trace.load(os.path.join(DATA, "small.xplane.pb")), hlo


def test_recorded_trace_planes_and_spans(chip):
    t, hlo = chip
    assert list(t.devices) == [0]
    assert [e.name for e in t.host].count("bench.step") == 3
    runs = [m for m in t.modules[0] if m.name.startswith(trace.module_name(hlo) + "(")]
    assert len(runs) == 3
    lo, hi = t.window()
    assert 0 < trace.busy_ns(t.devices[0], lo, hi) < hi - lo


def test_recorded_trace_device_clock_runs_ahead_of_the_host(chip):
    """On the chip's trace the device's timeline sits up to about a
    millisecond before the host's: the first step's program appears to run
    before the window that dispatched it opened, and is not counted.  Over
    a 20 s window that is 0.005%."""
    t, hlo = chip
    lo, _ = t.window()
    first = min(m.start for m in t.modules[0])
    assert 0 < lo - first < 2_000_000
    events = trace.kernel_events(t, hlo, "flash_attention")
    assert len(events) == 2
    assert all(k.operands[0].shape == (15, 512, 64) for _, k in events)


def test_recorded_trace_readers(chip):
    t, hlo = chip
    lo, hi = t.window()
    ctx = harness.Reading(llama, {}, {}, 1, PEAKS, 3, (hi - lo) * 1e-9, t, hlo)
    flops, _ = flash_attn_fwd_roofline.cost(
        trace.kernel_events(t, hlo, "flash_attention")[0][1])
    assert flops == 15 * 512 * 513 / 2 * 2 * 128
    assert 0 < flash_attn_fwd_roofline.read(ctx) <= 100
    assert 0 < device_idle_share.read(ctx) < 100
    b = harness.breakdown(t, 0, hlo)
    assert any("(Pallas flash_attention)" in n for n, _ in b["device_ops"])
    assert any("(Pallas rmsnorm)" in n for n, _ in b["device_ops"])
