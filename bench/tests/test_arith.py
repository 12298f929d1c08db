"""Operations and bytes from shapes, against counts made by hand."""
import json
import os

import pytest

from harness.arith import Dense, decode_step_floor_s, stream_bytes
from harness.common import BENCH


@pytest.fixture
def internlm2():
    with open(os.path.join(BENCH, "configs", "internlm2-1.8b.json")) as f:
        return Dense.from_config(json.load(f))


def test_internlm2_has_its_published_parameter_count(internlm2):
    # 24 layers x (wq 2048*2048 + wk, wv 2048*1024 each + wo 2048*2048
    # + 3 * 2048*8192 + two norms) + embedding and head 92544*2048 each
    # + the final norm: the 1.89 B of the model card.
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192 + 2 * 2048
    assert internlm2.layer_params == layer == 62_918_656
    assert internlm2.params == 24 * layer + 2 * 92544 * 2048 + 2048
    assert 1.88e9 < internlm2.params < 1.90e9


def test_prefill_and_decode_flops_by_hand():
    m = Dense(layers=1, d_model=4, heads=2, kv_heads=1, head_dim=2, d_ff=8,
              vocab=10)
    mm = 4 * 2 * 2 * 2 + 4 * 1 * 2 * 2 + 3 * 4 * 8      # 144 weights
    # seq 3: matmuls 2*144 per token; attention 4*heads*hd per key over
    # 1 + 2 + 3 causal keys; logits 2*4*10 at the last position only.
    assert m.prefill_flops(1, 3) == 3 * 2 * mm + 4 * 2 * 2 * 6 + 80
    assert m.prefill_flops(5, 3) == 5 * m.prefill_flops(1, 3)
    # decode at position 7 attends to 8 keys and computes logits.
    assert m.decode_flops(2, 7) == 2 * (2 * mm + 4 * 2 * 2 * 8 + 80)


def test_decode_bytes_and_floor_by_hand():
    m = Dense(layers=2, d_model=4, heads=2, kv_heads=1, head_dim=2, d_ff=8,
              vocab=10)
    weights = (2 * m.layer_params + 4 * 10 + 4) * 2
    cache = 3 * (2 * 2 * 1 * 2) * (5 + 2) * 2          # read 6, write 1
    want = weights + 3 * 4 * 2 + cache + 3 * 10 * 2
    assert m.decode_bytes(3, 5) == want
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert decode_step_floor_s(m, 3, 5, peaks) == pytest.approx(
        max(m.decode_flops(3, 5) / 1e9, want / 1e6))


def test_stream_bytes():
    assert stream_bytes("read", (262144, 1024), 4) == 1 << 30
    assert stream_bytes("write", (262144, 1024), 4) == 2 << 30
