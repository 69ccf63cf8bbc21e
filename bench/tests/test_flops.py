"""Operations and bytes at stablelm-3b widths against counts by hand."""
import flops
from reference.decoder import Dims

D = Dims(layers=32, d_model=2560, heads=32, kv_heads=32, head_dim=80,
         d_ff=6912, vocab=50304, rope_theta=1e4, eps=1e-5, b_in=128,
         b_out=128, sparsity=0.8)


def test_kept_blocks():
    # W_gate/W_up: 20 block-rows of 128 keep 4; W_down: 54 keep 11
    assert (D.nnz_up, D.nnz_down) == (4, 11)


def test_served_token():
    attn = 2 * 2560 * 80 * 128                   # q, k, v, o: 52,428,800
    mlp = 2 * (2 * 4 * 128 * 6912) + 2 * 11 * 128 * 2560
    assert mlp == 21_364_736
    head = 2 * 2560 * 50304                      # 257,556,480
    assert flops.served_body_flops(D) == 32 * (attn + mlp) == 2_361_393_152
    assert flops.served_token_flops(D) == 2_618_949_632 == \
        2_361_393_152 + head


def test_train_token():
    seq = 4096
    attn = 2 * 2560 * 80 * 128 + 4 * 32 * 80 * (seq + 1) / 2
    mlp_kept = 21_364_736
    mlp_dense = 3 * 2 * 2560 * 6912
    head = 2 * 2560 * 50304
    want = 32 * (3 * attn + 2 * mlp_kept + mlp_dense) + 3 * head
    assert flops.train_token_flops(D, seq) == int(want) == 12_584_321_024


def test_decode_step_bytes():
    attn = 2560 * 80 * 128 * 2                   # bf16
    mlp = (2 * 54 * 4 + 20 * 11) * 128 * 128 * 2 + (2 * 54 * 4 + 20 * 11) * 4
    norms = 4 * 2560 * 2
    glob = 2 * 2560 * 50304 * 2 + 2 * 2560 * 2
    weights = 32 * (attn + mlp + norms) + glob
    assert flops.packed_weight_bytes(D) == weights == 2_877_255_168
    kv = 2 * 2 * 32 * 32 * 80                    # 327,680 B per position
    assert flops.kv_bytes_per_position(D) == kv
    assert flops.decode_step_bytes(D, 16, 1024) == weights + 16 * 1024 * kv
