"""FLOP and byte counts of the flash-attention kernel at the cells' shapes,
and the MFU arithmetic."""
import pytest

from bench.configs import llama
from bench.harness import load_json
from bench.metrics import flash_attn_fwd_roofline as flash
from bench.metrics import mfu
from bench.trace import Array, Kernel

V5E = load_json("bench", "peaks.json")["devices"]["TPU v5 lite"]


def _flash(bh, bhkv, s, d, space=0):
    return Kernel("k", ("flash_attention",), Array("bf16", (bh, s, d), space),
                  [Array("bf16", (bh, s, d), space), Array("bf16", (bhkv, s, d), space),
                   Array("bf16", (bhkv, s, d), space)])


@pytest.mark.parametrize("name,bh,bhkv,s,d", [
    ("smollm-360m.train-2k", 2 * 15, 2 * 5, 2048, 64),     # m=4: 2 rows a call
    ("smollm-360m.train-4k", 1 * 15, 1 * 5, 4096, 64),     # m=4: 1 row a call
    ("deepseek-7b-stage.train-2k", 2 * 32, 2 * 32, 2048, 128),
])
def test_flash_attention_cost(name, bh, bhkv, s, d):
    flops, nbytes = flash.cost(_flash(bh, bhkv, s, d))
    assert flops == bh * s * (s + 1) / 2 * 2 * (2 * d)
    assert nbytes == 2 * (2 * bh * s * d + 2 * bhkv * s * d)
    # compute-bound at every cell's shapes: the FLOP roof is the higher one
    assert flops / V5E["bf16_flops"] > nbytes / V5E["hbm_bytes_per_s"]


def test_flash_attention_counts_only_hbm_bytes():
    flops, nbytes = flash.cost(_flash(30, 10, 2048, 64, space=1))
    assert nbytes == 0 and flops > 0


def test_flash_attention_refuses_cross_attention():
    k = _flash(2, 2, 8, 4)
    k.operands[1] = Array("bf16", (2, 16, 4), 0)
    with pytest.raises(ValueError):
        flash.cost(k)


def test_smollm_step_flops():
    sizes = load_json("bench", "configs", "smollm-360m.json")["sizes"]
    # 361.8M matmul parameters: 32 layers of 9.83M plus the tied head once
    params = 32 * (960 * 960 * 2 + 2 * 960 * 320 + 3 * 960 * 2560) + 960 * 49152
    assert params == 361_758_720
    attention = 32 * 8 * 12 * 960 * (2048 * 2049 / 2)
    assert llama.step_flops(sizes, 8, 2048) == 6 * params * 8 * 2048 + attention
    # the same tokens at 4 x 4096 carry twice the attention work
    att4 = llama.step_flops(sizes, 4, 4096) - 6 * params * 4 * 4096
    assert att4 == pytest.approx(2 * attention, rel=1e-3)


def test_deepseek_stage_step_flops():
    sizes = load_json("bench", "configs", "deepseek-7b-stage.json")["sizes"]
    params = 2 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 12800
    assert llama.step_flops(sizes, 8, 2048) == pytest.approx(
        6 * params * 16384 + 2 * 8 * 12 * 4096 * 2048 * 2049 / 2)


def test_mfu_is_flops_over_window_chips_peak():
    class Ctx:
        reference = llama
        sizes = load_json("bench", "configs", "smollm-360m.json")["sizes"]
        traffic = {"global_batch": 8, "seq_len": 2048}
        chips, steps, window_s = 1, 10, 20.0
        peaks = V5E
    want = 100 * 10 * llama.step_flops(Ctx.sizes, 8, 2048) / (20.0 * 197e12)
    assert mfu.read(Ctx) == pytest.approx(want)
    Ctx.steps = 0
    assert mfu.read(Ctx) is None
