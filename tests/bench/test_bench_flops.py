"""The FLOP and byte counts equal hand counts at smoke widths."""
import json

from smoke import DATA
from bench import flops


def _config(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_dlrm_train_flops_hand_count():
    c = _config("dlrm-rm2-smoke")        # 13-32-16 bottom, 26 fields, d=16
    assert c["bot_mlp"] == [32, 16] and c["top_mlp"] == [64, 32, 1]
    bot_fwd = 2 * (13 * 32 + 32 * 16)
    bot_bwd = 2 * bot_fwd - 2 * 13 * 32          # no gradient for dense input
    pairs = 27 * 26 // 2                          # 351
    inter = 2 * pairs * 16
    top_fwd = 2 * ((pairs + 16) * 64 + 64 * 32 + 32 * 1)
    want = bot_fwd + bot_bwd + 3 * inter + 3 * top_fwd
    assert flops.train_flops_per_example(c) == want


def test_xdeepfm_train_flops_hand_count():
    c = _config("xdeepfm-smoke")          # 12 fields, d=8, CIN 24-24, DNN 32-32
    F, d = 12, 8
    assert c["cin_layers"] == [24, 24] and c["deep_mlp"] == [32, 32]
    cin = 0
    for hk, ho in ((F, 24), (24, 24)):
        cin += 5 * hk * F * d + 3 * 2 * ho * hk * F * d
    out_fwd = 2 * 48
    deep_fwd = 2 * (F * d * 32 + 32 * 32 + 32)
    want = cin + 3 * out_fwd + 3 * deep_fwd
    assert flops.train_flops_per_example(c) == want


def test_lookup_bytes_hand_count():
    c = _config("dlrm-rm2-smoke")         # max_set 16, d=16, 26 fields
    per_id = 4 + 16 * 4 + 4 + 16 * 4 + 16 * 4
    assert flops.lookup_bytes(c, 64) == 64 * 26 * per_id
    x = _config("xdeepfm-smoke")           # main pool d=8, linear d=1
    per_id = (4 + 64 + 4 + 32 + 32) + (4 + 64 + 4 + 4 + 4)
    assert flops.lookup_bytes(x, 10) == 10 * 12 * per_id
