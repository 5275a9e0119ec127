"""The shared reader of the program's scopes (``bench/scopes.py``) and the
metrics that read it, on a hand-written HLO fragment in the form the TPU
compiler prints and a device timeline built by hand."""
import pytest

from bench import harness, scopes, trace
from bench.metrics import (attn_bwd_ms, head_loss_ms, optimizer_ms,
                           pipeline_runtime_ms, recompute_share)

J = "jit(train_step)"
STAGE = "while/body/closed_call/checkpoint/stage/while/body/closed_call"
ATTN_BWD = f"{J}/transpose(jvp(pipe))/{STAGE}/attn/transpose(attn)/jvp()/dot_general"
ATTN_REMAT = (f"{J}/transpose(jvp(pipe))/while/body/closed_call/checkpoint/"
              "rematted_computation/stage/while/body/closed_call/attn/pallas_call")
HLO = f"""HloModule jit_train_step, entry_computation_layout={{()->f32[]}}

%fused_computation.1 (param_0: bf16[8,4], param_1: bf16[4,4]) -> bf16[2,8,4] {{
  %param_0 = bf16[8,4]{{1,0}} parameter(0)
  %param_1 = bf16[4,4]{{1,0}} parameter(1)
  %convolution.1 = bf16[8,4]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{ATTN_BWD}"}}
  ROOT %dynamic-update-slice.1 = bf16[2,8,4]{{2,1,0}} dynamic-update-slice(%convolution.1), metadata={{op_name="{J}/transpose(jvp(pipe))/while/body/dynamic_update_slice"}}
}}

%fused_computation.2 (param_0.2: bf16[2,8,4]) -> bf16[2,8,4] {{
  ROOT %select.2 = bf16[2,8,4]{{2,1,0}} select(%param_0.2), metadata={{op_name="{J}/jvp(pipe)/while/body/select_n"}}
}}

%fused_computation.3 (param_0.3: bf16[8,4]) -> f32[8,16] {{
  %convolution.3 = f32[8,16]{{1,0}} convolution(%param_0.3), metadata={{op_name="{J}/jvp(head_loss)/while/body/closed_call/dot_general"}}
  ROOT %exp.3 = f32[8,16]{{1,0}} exponential(%convolution.3), metadata={{op_name="{J}/jvp(head_loss)/while/body/closed_call/exp"}}
}}

%fused_computation.4 (param_0.4: f32[8,16]) -> f32[8,4] {{
  ROOT %fusion.40 = f32[8,4]{{1,0}} fusion(%param_0.4), kind=kOutput, calls=%fused_computation.3, metadata={{op_name="{J}/transpose(jvp(head_loss))/while/body/closed_call/checkpoint/mul"}}
}}

ENTRY %main.9 (p0: bf16[8,4], p1: bf16[4,4]) -> f32[] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  %p1 = bf16[4,4]{{1,0}} parameter(1)
  %fusion.680 = bf16[2,8,4]{{2,1,0:T(8,128)(2,1)S(1)}} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{J}/transpose(jvp(pipe))/while/body/dynamic_update_slice"}}
  %fusion.2 = bf16[2,8,4]{{2,1,0}} fusion(%fusion.680), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{J}/jvp(pipe)/while/body/select_n"}}
  %closed_call.77 = bf16[8,4]{{1,0}} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={{op_name="{ATTN_REMAT}"}}
  %fusion.4 = f32[8,4]{{1,0}} fusion(%p0), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{J}/transpose(jvp(head_loss))/mul"}}
  %multiply.5 = f32[4,4]{{1,0}} multiply(%p1, %p1), metadata={{op_name="{J}/optimizer/jit(norm)/mul"}}
  %while.9 = (s32[]{{:T(128)}}, bf16[2,8,4]{{2,1,0}}) while(%p0), condition=%c, body=%b, metadata={{op_name="{J}/jvp(pipe)/while"}}
  %copy-start.1 = (bf16[8,4]{{1,0}}, u32[]) copy-start(%p0)
  ROOT %r = f32[] constant(0)
}}
"""


def test_scopes_inside_transforms_and_paths():
    assert scopes.scopes(ATTN_BWD) == ("pipe", "stage", "attn", "attn")
    assert scopes.scopes(f"{J}/jvp(head_loss)/while/body/closed_call/"
                         "norm/pallas_call") == ("head_loss", "norm")
    assert scopes.scopes(f"{J}/transpose(jvp(embed))/jit(_take)/scatter-add") \
        == ("embed",)
    # a jitted function's name is not a scope, even one spelled like it
    assert scopes.scopes(f"{J}/optimizer/jit(norm)/sqrt") == ("optimizer",)
    assert scopes.scopes(f"{J}/jvp()/squeeze") == ()
    assert scopes.scopes("") == ()


def test_a_fusion_takes_the_op_name_of_its_matrix_product():
    names = scopes.op_names(HLO)
    assert names["fusion.680"] == ATTN_BWD                   # not its DUS root
    assert names["fusion.2"].endswith("jvp(pipe)/while/body/select_n")
    # the product of a fusion nested in the one called
    assert names["fusion.4"].endswith("jvp(head_loss)/while/body/closed_call/"
                                      "dot_general")
    assert names["closed_call.77"] == ATTN_REMAT
    assert names["copy-start.1"] == ""


def test_phases():
    assert scopes.phase(ATTN_REMAT) == "recompute"
    assert scopes.phase(ATTN_BWD) == "backward"
    assert scopes.phase(f"{J}/jvp(pipe)/{STAGE}/mlp/dot_general") == "forward"
    assert scopes.phase(f"{J}/transpose(jvp(head_loss))/while/body/closed_call/"
                        "checkpoint/rematted_computation/dot_general") == "recompute"
    # the fused executor's backward task recomputes its stage's forward
    assert scopes.phase(f"{J}/pipe/while/body/closed_call/cond/branch_1_fun/"
                        "pipe_b/jvp(stage)/while/body/attn/dot_general") == "recompute"
    assert scopes.phase(f"{J}/pipe/while/body/closed_call/cond/branch_1_fun/"
                        "pipe_b/transpose(jvp(stage))/while/body/attn/"
                        "dot_general") == "backward"
    assert scopes.phase(f"{J}/pipe/while/body/closed_call/pipe_f/stage/while/"
                        "body/attn/dot_general") == "forward"


def _ctx(steps=2):
    """Two steps in a 10 us window, each one module run: the loop (1000 ns,
    holding the attention backward fusion of 300 ns and the select of
    100 ns), the recomputed flash call (200 ns), the head's backward
    (150 ns) and the optimizer (50 ns).  One more attention fusion runs
    in another program, which no metric may count."""
    devs, mods = [], []
    for s in (1_000, 6_000):
        mods.append(trace.Event("jit_train_step(5)", s, 2_000))
        devs += [trace.Event("while.9", s, 1_000),
                 trace.Event("fusion.680", s + 100, 300),
                 trace.Event("fusion.2", s + 500, 100),
                 trace.Event("closed_call.77", s + 1_000, 200),
                 trace.Event("fusion.4", s + 1_200, 150),
                 trace.Event("multiply.5", s + 1_400, 50)]
    mods.append(trace.Event("jit_other(6)", 9_000, 1_000))
    devs.append(trace.Event("fusion.680", 9_000, 500))
    t = trace.Trace({0: sorted(devs, key=lambda e: e.start)},
                    [trace.Event("bench.window", 1_000, 10_000)], {0: mods})
    return harness.Reading(None, {}, {}, 1, {}, steps, 10e-6, t, HLO)


def test_readers_sum_self_time_per_step():
    ctx = _ctx()
    assert attn_bwd_ms.read(ctx) == pytest.approx(300e-6)
    assert head_loss_ms.read(ctx) == pytest.approx(150e-6)
    assert optimizer_ms.read(ctx) == pytest.approx(50e-6)
    # busy 2 x 1400 ns and the other program's 500 ns, of which 2 x 200 ns
    # the recomputed flash call
    assert recompute_share.read(ctx) == pytest.approx(100 * 400 / 3_300)


def test_pipeline_runtime_excludes_model_scopes():
    # the loop's own 600 ns and the select's 100 ns, not the attention
    # backward fused into the loop's stash write
    assert pipeline_runtime_ms.read(_ctx()) == pytest.approx(700e-6)


def test_none_where_the_scope_is_absent_zero_where_it_took_no_time():
    ctx = _ctx()
    absent = HLO.replace("/optimizer/", "/")
    assert optimizer_ms.read(harness.Reading(
        None, {}, {}, 1, {}, 2, 10e-6, ctx.trace, absent)) is None
    idle = trace.Trace({0: [e for e in ctx.trace.devices[0]
                            if e.name != "multiply.5"]},
                       ctx.trace.host, ctx.trace.modules)
    assert optimizer_ms.read(harness.Reading(
        None, {}, {}, 1, {}, 2, 10e-6, idle, HLO)) == 0.0
    untraced = harness.Reading(None, {}, {}, 1, {}, 2, 1.0, None, HLO)
    for reader in (attn_bwd_ms, head_loss_ms, optimizer_ms,
                   pipeline_runtime_ms, recompute_share):
        assert reader.read(untraced) is None
        if reader is not recompute_share:
            assert reader.read(_ctx(steps=0)) is None
