"""The yardstick's operation and byte counts, against hand-worked
values for both configurations."""
import spec
import work

OLMO = spec.load_config("olmo-1b")["arch"]
PHI = spec.load_config("phi4-mini")["arch"]


def test_olmo_counts():
    # q,k,v: 2048*128*48; out: 16*128*2048; MLP: 3*2048*8192
    assert work.layer_matmul_params(OLMO) == 12_582_912 + 4_194_304 \
        + 50_331_648 == 67_108_864
    assert work.head_params(OLMO) == 103_022_592
    # OLMo-1B has no norm parameters: the matmuls are the whole model
    assert work.matmul_params(OLMO) == 1_176_764_416
    assert work.weight_bytes(OLMO) == 2_353_528_832
    assert work.kv_bytes_per_token(OLMO) == 16 * 2 * 16 * 128 * 2 == 131_072


def test_phi4_mini_counts():
    # q,k,v: 3072*128*(24+16); out: 24*128*3072; MLP: 3*3072*8192
    assert work.layer_matmul_params(PHI) == 15_728_640 + 9_437_184 \
        + 75_497_472 == 100_663_296
    assert work.head_params(PHI) == 614_596_608
    assert work.matmul_params(PHI) == 3_835_822_080
    assert work.weight_bytes(PHI) == 7_671_644_160
    assert work.kv_bytes_per_token(PHI) == 32 * 2 * 8 * 128 * 2 == 131_072


def test_decode_flops_and_bytes():
    # one olmo token at context 1000: 2 FLOPs a weight, plus QK^T and AV
    # over 1000 positions in 16 layers of 16 heads of 128
    assert work.decode_flops(OLMO, [1000]) == 2 * 1_176_764_416 \
        + 16 * 4 * 16 * 128 * 1000 == 2_484_600_832
    # two steps read the weights twice and the KV of each token's context
    assert work.decode_bytes(OLMO, 2, [100, 300]) == \
        2 * 2_353_528_832 + 400 * 131_072
    assert work.decode_flops(PHI, []) == 0


def test_prefill_flops_count_real_tokens_and_one_projection():
    n = 1024
    want = (2 * 16 * 67_108_864 * n + 16 * 4 * 16 * 128 * (n * (n + 1) // 2)
            + 2 * 103_022_592)
    assert work.prefill_flops(OLMO, [n]) == want
    # per request: a padded bucket is not the algorithm's work
    assert work.prefill_flops(OLMO, [700]) < work.prefill_flops(OLMO, [1024])
    two = work.prefill_flops(PHI, [1500, 3000])
    assert two == work.prefill_flops(PHI, [1500]) \
        + work.prefill_flops(PHI, [3000])
