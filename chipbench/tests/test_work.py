"""Work counts and the peak table against hand-worked numbers at the
published widths."""
import json
import os

import pytest

from chipbench import peaks, work
from chipbench.harness import ROOT


@pytest.fixture(scope="module")
def yi():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "yi-6b-serve.json")) as f:
        return json.load(f)["model"]


def test_yi6b_token_flops(yi):
    # per layer: q,k,v: 2*4096*(4096 + 2*512) = 41,943,040
    #            o:     2*4096*4096          = 33,554,432
    #            ffn:   3 * 2*4096*11008     = 270,532,608
    # sum 346,030,080 x 32 layers
    assert work.lm_token_flops(yi) == 346_030_080 * 32
    assert work.lm_head_flops(yi) == 2 * 4096 * 64000 == 524_288_000


def test_yi6b_attention_work_per_key(yi):
    # q.K and p.V: 2 * 2 * 32 heads * 128 per key and layer, 32 layers
    assert work.lm_attn_flops(yi, 1) == 16_384 * 32
    # K and V of 4 kv heads x 128 in bf16: 2 * 4 * 128 * 2 B = 2 KiB a
    # layer, 64 KiB a token over 32 layers
    assert work.lm_attn_bytes(yi, 1) == 65_536
    assert work.lm_attn_bytes(yi, 1000) == 65_536_000


def test_vit1b_train_flops():
    # per layer and token: q,k,v,o 4 * 2*2048*2048 = 33,554,432 and the
    # MLP 2 * 2*2048*8192 = 67,108,864; attention 2*2 * 65*65 * 2048 =
    # 34,611,200 a layer; 65 tokens x 24 layers; the patch projection
    # 64 * 2*48*2048 = 12,582,912 and the head 2*2048*10 = 40,960
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "vit-1b-tp4.json")) as f:
        m = json.load(f)["model"]
    fwd = 24 * (65 * 100_663_296 + 34_611_200) + 12_582_912 + 40_960
    assert work.vit_forward_flops(m) == fwd == 157_878_034_432
    assert work.vit_train_flops(m) == 3 * fwd


def test_peak_table_knows_v5e_and_refuses_others():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
