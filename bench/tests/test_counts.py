"""FLOPs and bytes against hand arithmetic at a small shape."""
from bench.bytes import decode_bytes, decode_weight_bytes
from bench.dims import Dims
from bench.flops import decode_flops, layer_matmul_params, prefill_flops

D = Dims(name="t", n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
         head_dim=2, d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-5,
         qk_norm=False, tie_embeddings=False)


def test_layer_params():
    # wq 8*4*2 + wk 8*2*2 + wv 8*2*2 + wo 4*2*8 = 64+32+32+64; mlp 3*8*16
    assert layer_matmul_params(D) == 192 + 384


def test_prefill_flops():
    # 3 tokens from position 0: keys 1+2+3 = 6; per key 4*4*2 = 32
    assert prefill_flops(D, 0, 3) == 2 * (2 * 576 * 3 + 32 * 6)
    # 2 tokens from position 5: keys 6+7 = 13
    assert prefill_flops(D, 5, 2) == 2 * (2 * 576 * 2 + 32 * 13)


def test_decode_flops():
    # 2 rows attending 4 and 9 keys; each row also pays the unembed 8*10
    per_row = 2 * (2 * 576 + 80)
    assert decode_flops(D, [4, 9]) == 2 * per_row + 2 * 32 * 13


def test_decode_bytes():
    # weights: 2 layers x (576 + 2 norms of 8) + unembed 80 + final norm 8
    # + 2 looked-up embedding rows of 8, all bf16
    w = 2 * (2 * (576 + 16) + 80 + 8 + 2 * 8)
    assert decode_weight_bytes(D, 2) == w
    kv = 2 * 2 * 2 * 2 * 2          # layers x (k, v) x K x dh x bf16
    assert D.kv_bytes_per_token == kv
    assert decode_bytes(D, [4, 9]) == w + kv * 13 + kv * 2
