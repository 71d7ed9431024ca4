"""flops.py against a hand count (one multiply-add = 2 operations)."""

import json
import os

from vbench import flops, loader

CFG = os.path.join(loader.HERE, "configs")


def _sizes(name):
    with open(os.path.join(CFG, name)) as f:
        return json.load(f)


def test_resize_hand_count():
    # rows: [224,1080] x [1080, 1920*3]; columns: [224,1920] x [1920, 224*3]
    rows = 2 * 224 * 1080 * 1920 * 3
    cols = 2 * 224 * 1920 * 224 * 3
    assert rows == 2_786_918_400 and cols == 578_027_520
    assert flops.resize_flops(1080, 1920, 224) == rows + cols
    assert flops.resize_flops(224, 224, 224) == 0


def test_vit_b16_hand_count():
    cfg = _sizes("tagclip_fleet.json")
    # 197 tokens a frame; per layer: q/k/v 697,171,968 + scores 59,610,624
    # + context 59,610,624 + output 232,390,656 + the two MLP products
    # 1,859,125,248
    layer = 697_171_968 + 2 * 59_610_624 + 232_390_656 + 1_859_125_248
    assert layer == 2_907_909_120
    embed = 231_211_008                 # 196 patches x 768 pixels x 768
    head = 1_536_000
    want = 3_364_945_920 + embed + 12 * layer + head
    assert flops.sample_flops("vit", cfg, 1080, 1920) == want
    # 35.1 GFLOP of model (17.6 GMACs, as published) plus 3.4 of resize
    assert want == 38_492_602_368


def test_videomae_b_hand_count():
    cfg = _sizes("videomae_b.json")
    # 784 tokens a clip ((8 / 2) x 14 x 14); per layer: q/k/v 2,774,532,096
    # + scores 944,111,616 + context 944,111,616 + output 924,844,032
    # + MLP 7,398,752,256
    layer = (2_774_532_096 + 2 * 944_111_616 + 924_844_032
             + 7_398_752_256)
    assert layer == 12_986_351_616
    embed = 1_849_688_064               # 784 tubelets x 1536 pixels x 768
    want = 8 * 3_364_945_920 + embed + 12 * layer + 614_400
    assert flops.sample_flops("videomae", cfg, 1080, 1920) == want
    assert want == 184_606_089_216
