"""The cost functions against hand arithmetic at the tests' tiny shape
(d 64, 4 heads, 2 KV heads, head 16, MLP 128, 2 layers, vocabulary 512;
RM: 64 omega rows, 42 feature columns). CPU only."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import loader  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def tiny():
    return {m: json.loads((DATA / f"tiny-{m}.json").read_text())
            for m in ("rm", "exact")}


def test_rm_plan_sizes(tiny):
    plan = loader.reference(tiny["rm"]).rm_plan(tiny["rm"]["rm"])
    # D = 64 over a_n = 1/n!: counts 24, 24, 12, 4, 1 for degrees 0..4;
    # the 24 constant features collapse into one column
    assert plan["degrees"] == [1, 2, 3, 4]
    assert plan["counts"] == [24, 12, 4, 1]
    assert plan["total_rows"] == 24 + 2 * 12 + 3 * 4 + 4
    assert plan["output_dim"] == 1 + 24 + 12 + 4 + 1


def test_model_forward(tiny):
    mf = loader.cost("model_forward")
    ex, rm = tiny["exact"], tiny["rm"]
    # 2 layers x 2 x (q 64*64 + k,v 2*64*32 + o 64*64 + mlp 3*64*128)
    assert mf.dense_per_token(ex) == 147456
    assert mf.head(ex) == 2 * 64 * 512
    # causal attention: 2 layers x 2 h dh T (T + 1) at T = 10
    assert mf.prefill(ex, 10) == 10 * 147456 + 2 * 2 * 4 * 16 * 110 + 65536
    # lanes at 5 and 7 positions: 2 layers x 4 h dh positions
    assert mf.decode(ex, [5, 7]) == 2 * (147456 + 65536) + 512 * 12
    # rm, one token one layer: featurize (4 + 2) vectors over 64 rows, the
    # KV heads' state update, the query heads' readout and normaliser
    per = 2 * 64 * 16 * 6 + 2 * (2 * 42 * 16 + 42) + 4 * (2 * 42 * 16 + 84)
    assert mf.rm_attention_per_token(rm) == per == 20772
    assert mf.prefill(rm, 10) == 10 * 147456 + 10 * 2 * per + 65536
    assert mf.decode(rm, [5, 7]) == 2 * (147456 + 2 * per + 65536)


def test_decode_step_bytes(tiny):
    ds = loader.cost("decode_step")
    ex, rm = tiny["exact"], tiny["rm"]
    layer = 64 * 64 * 2 + 2 * 64 * 32 + 3 * 64 * 128 + 2 * 64 + 2 * 16
    assert ds.parameters(ex) == 2 * (2 * layer + 512 * 64 + 64) == 213760
    assert ds.parameters(rm) == 2 * (2 * (layer + 64 * 16 + 1) + 512 * 64
                                     + 64)
    # exact: keys and values of 12 positions, 2 KV heads, 2 layers, 2 B
    assert ds.state(ex, [5, 7]) == 2 * 2 * 2 * 2 * 16 * 12
    # rm: S (42 x 16) and n (42) of 2 KV heads, read and written, 2 lanes
    assert ds.state(rm, [5, 7]) == 2 * 2 * 2 * 2 * 2 * (42 * 16 + 42)
    assert ds.step(ex, [5, 7]) == 213760 + 3072
    # OLMo: no norm or qk-norm scales, 4 KV heads of 16
    olmo = json.loads((DATA / "tiny-olmo-rm.json").read_text())
    assert ds.parameters(olmo) == 2 * (2 * (4 * 64 * 64 + 3 * 64 * 128
                                            + 64 * 16 + 1) + 512 * 64)


def test_rm_kernels(tiny):
    rm = tiny["rm"]
    pre = loader.cost("rm_attn_prefill")
    dec = loader.cost("rm_attn_decode")
    assert pre.flops(rm, 10) == 10 * 20772
    # q 4 heads, k and v 2 KV heads, omegas, output, final state; 2 B
    assert pre.bytes_moved(rm, 10) == 2 * (10 * 16 * 8 + 64 * 16
                                           + 10 * 4 * 16 + 2 * (42 * 16 + 42))
    # 2 lanes x (4 + 2) vectors x 64 rows x 16
    assert dec.flops(rm, 2) == 2 * 12 * 64 * 16
    assert dec.bytes_moved(rm, 2) == 2 * (12 * (16 + 42) + 64 * 16)


def test_peaks_table():
    p = loader.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        loader.peaks("some other chip")
