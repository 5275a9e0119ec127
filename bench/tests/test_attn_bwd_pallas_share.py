"""``attn_bwd_pallas_share`` on a hand-written HLO fragment in the form the
TPU compiler prints and a device timeline built by hand: the attention
backward in XLA ops alone (the blocked reference's VJP), and with the
Pallas backward kernels beside the projections' backward."""
import base64

import pytest

from bench import harness, trace
from bench.metrics import attn_bwd_pallas_share

J = "jit(train_step)/transpose(jvp(pipe))/while/body/closed_call/checkpoint"
BWD = f"{J}/stage/attn"


def _body(name):
    """A Mosaic body whose debug strings name the kernel, as the real
    serialized module does."""
    return base64.b64encode(f"func.func {name} loc(kernels)".encode()).decode()


def _hlo(kernels):
    calls = "\n".join(
        f'  %{n}.1 = bf16[8,4]{{1,0}} custom-call(%p0, %p1), '
        f'custom_call_target="tpu_custom_call", metadata={{op_name='
        f'"{BWD}/{n}/pallas_call"}}, backend_config={{"custom_call_config":'
        f'{{"body":"{_body(n)}"}}}}' for n in kernels)
    return f"""HloModule jit_train_step, entry_computation_layout={{()->f32[]}}

%fused_computation.1 (param_0: bf16[8,4], param_1: bf16[4,4]) -> bf16[8,4] {{
  %param_0 = bf16[8,4]{{1,0}} parameter(0)
  %param_1 = bf16[4,4]{{1,0}} parameter(1)
  ROOT %convolution.1 = bf16[8,4]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{BWD}/transpose(attn)/dot_general"}}
}}

ENTRY %main.9 (p0: bf16[8,4], p1: bf16[4,4]) -> f32[] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  %p1 = bf16[4,4]{{1,0}} parameter(1)
  %fusion.1 = bf16[8,4]{{1,0}} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{BWD}/transpose(attn)/dot_general"}}
  %multiply.5 = f32[4,4]{{1,0}} multiply(%p1, %p1), metadata={{op_name="jit(train_step)/optimizer/mul"}}
{calls}
  ROOT %r = f32[] constant(0)
}}
"""


def _ctx(hlo, events, steps=2):
    """``events`` (name, duration in ns) run once a step, back to back, in
    two steps of the compiled step inside a 10 us window."""
    devs, mods = [], []
    for s in (1_000, 6_000):
        mods.append(trace.Event("jit_train_step(5)", s, 3_000))
        t = s
        for name, dur in events:
            devs.append(trace.Event(name, t, dur))
            t += dur
    t = trace.Trace({0: devs}, [trace.Event("bench.window", 1_000, 10_000)],
                    {0: mods})
    return harness.Reading(None, {}, {}, 1, {}, steps, 10e-6, t, hlo)


def test_zero_where_the_backward_is_all_xla():
    ctx = _ctx(_hlo(()), [("fusion.1", 900), ("multiply.5", 50)])
    assert attn_bwd_pallas_share.read(ctx) == 0.0


def test_the_kernels_share_of_the_attention_backward():
    hlo = _hlo(attn_bwd_pallas_share.KERNELS)
    ctx = _ctx(hlo, [("fusion.1", 100), ("attention_bwd_stats.1", 300),
                     ("attention_bwd_grads.1", 500), ("multiply.5", 50)])
    assert attn_bwd_pallas_share.read(ctx) == pytest.approx(100 * 800 / 900)


def test_none_without_a_trace_or_an_attention_backward():
    hlo = _hlo(attn_bwd_pallas_share.KERNELS)
    untraced = harness.Reading(None, {}, {}, 1, {}, 2, 1.0, None, hlo)
    assert attn_bwd_pallas_share.read(untraced) is None
    unscoped = hlo.replace("attn", "mlp")
    ctx = _ctx(unscoped, [("fusion.1", 100), ("attention_bwd_stats.1", 300)])
    assert attn_bwd_pallas_share.read(ctx) is None
