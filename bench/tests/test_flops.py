"""Operation and byte counts against hand counts at small shapes."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops  # noqa: E402

# d=8, f=16, L=2, H=4, kvH=2, hd=2, V=10
C = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2, "vocab_size": 10}


def test_layer_matmul_weights():
    # q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 + gate/up/down 3*8*16
    assert flops.shape(C)["layer_mm"] == 64 + 64 + 64 + 384


def test_decode_attention_counts():
    # 5 positions: QK and PV, 2 ops each, per head dim, per head
    f, b = flops.decode_attention(C, 5, page=4)
    assert f == 4 * 4 * 2 * 5
    # two pages of 4 rows, K and V, kvH 2 x hd 2 x 2 bytes; q in + out
    assert b == 2 * 2 * 4 * 2 * 2 * 2 + 2 * 4 * 2 * 2


def test_prefill_chunk_counts():
    # rows at positions 3, 4, 5 see 4, 5, 6 positions
    f, b = flops.prefill_chunk_attention(C, 3, 3, page=4)
    assert f == 4 * 4 * 2 * (4 + 5 + 6)
    assert b == 2 * 2 * 4 * 2 * 2 * 2 + 2 * 3 * 4 * 2 * 2
    assert list(flops.chunks(5, 75, 32)) == [(5, 32), (37, 32), (69, 6)]


def test_model_counts():
    mm = 64 + 64 + 64 + 384
    assert flops.decode_token(C, 5) == 2 * (2 * mm + 10 * 8) + 2 * 4 * 4 * 2 * 5
    assert flops.prefill_rows(C, 0, 3) == sum(flops.prefill_row(C, p) for p in range(3))
    assert flops.unembed(C) == 2 * 10 * 8
    # batch 2 x seq 3: forward products per token, causal pairs 1+2+3, x3
    fwd = 2 * 2 * 3 * (2 * mm + 80) + 2 * 4 * 4 * 2 * 2 * 6
    assert flops.train_step(C, 2, 3) == 3 * fwd
