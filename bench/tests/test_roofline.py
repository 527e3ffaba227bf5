"""Work counts against hand arithmetic at OLMoE-1B-7B's widths, and the
peaks table."""

import pytest

from bench import arch
from bench.roofline import kernels, work

K = kernels.load_all()

D, F, E, H, HD, V = 2048, 1024, 64, 16, 128, 50304
TILE = 3 * D * F * 2                    # w1 [D, 2F] + w2 [F, D] in bf16


def test_tile_bytes():
    assert work.expert_tile_bytes(D, F, "bf16") == TILE == 12_582_912
    # int8 tiles carry f32 scale rows: s1 [2, F] and s2 [F]
    assert work.expert_tile_bytes(D, F, "int8") == 6_291_456 + 12_288


def test_moe_decode_needs_each_routed_expert_once():
    # 16 live slots at k=8 route 128 token-slots; 64 experts cap the reads
    flops, nbytes = K["moe_decode"].need([8] * 16, d=D, f=F, e=E,
                                         dtype="bf16")
    assert flops == 6 * D * F * 128 == 1_610_612_736
    assert nbytes == 64 * TILE + 2 * 16 * D * 2 == 805_437_440
    # 3 slots at k=4 route 12 token-slots: 12 tiles
    flops, nbytes = K["moe_decode"].need([4] * 3, d=D, f=F, e=E,
                                         dtype="int8")
    assert nbytes == 12 * (6_291_456 + 12_288) + 2 * 3 * D * 2


def test_flash_decode_paged_reads_the_live_kv():
    flops, nbytes = K["flash_decode_paged"].need([100, 200], heads=H,
                                            kv_heads=H, hd=HD)
    assert flops == 4 * 300 * H * HD == 2_457_600
    assert nbytes == 2 * 300 * H * HD * 2 + 2 * 2 * H * HD * 2 == 2_473_984


def test_moe_gmm_counts_routed_token_slots():
    flops, nbytes = K["moe_gmm"].need([8] * 128 + [4] * 10, d=D, f=F, e=E,
                                 dtype="bf16")
    routed = 8 * 128 + 4 * 10
    assert flops == 2 * 3 * D * F * routed
    assert nbytes == 64 * TILE + 2 * routed * D * 2


def test_least_time_takes_the_binding_peak():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_s"] == 819e9
    flops, nbytes = K["moe_decode"].need([8] * 16, d=D, f=F, e=E,
                                         dtype="bf16")
    assert work.least_seconds(flops, nbytes, pk) == pytest.approx(
        805_437_440 / 819e9)
    # a [16, 128] chunk at k=8 is still bandwidth-bound; twice that is not
    flops, nbytes = K["moe_gmm"].need([8] * 2048, d=D, f=F, e=E, dtype="bf16")
    assert work.least_seconds(flops, nbytes, pk) == nbytes / 819e9
    flops, nbytes = K["moe_gmm"].need([8] * 4096, d=D, f=F, e=E, dtype="bf16")
    assert work.least_seconds(flops, nbytes, pk) == flops / 197e12


def test_model_flops_per_token():
    m = {"hidden_size": D, "vocab_size": V, "num_attention_heads": H,
         "num_key_value_heads": H, "head_dim": HD, "num_experts": E,
         "intermediate_size": F}
    ks = (8, 6, 4, 4, 4, 4, 6, 8)
    per_layer = (2 * D * 3 * H * HD + 2 * H * HD * D + 4 * 1000 * H * HD
                 + 2 * D * E)
    want = 2 * D * V + 8 * per_layer + 6 * D * F * sum(ks)
    assert arch.load("olmoe").model_flops(m, ks, 1000) == want


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
