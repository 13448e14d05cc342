"""The benchmark's FLOP and byte counts against hand-worked values at the
published widths of Mixtral-8x7B."""
import json

import pytest
from bench_tiny import BENCH

from harness import flops

PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


MIXTRAL = config("mixtral-8x7b-2l")


def test_mixtral_decode_token():
    # per layer: projections 2*4096*128*(2*32 + 2*8) = 83,886,080;
    # router 2*4096*8 = 65,536; two experts 2 * 6*4096*14336 = 704,643,072;
    # scores over 100 keys 4*32*128*100 = 1,638,400. Two layers, then the
    # head 2*32000*4096 = 262,144,000.
    assert flops.attention_proj_flops(MIXTRAL) == 83_886_080
    assert flops.expert_flops(MIXTRAL) == 352_321_536
    assert flops.token_flops(MIXTRAL, 100, True) == \
        2 * (83_886_080 + 65_536 + 704_643_072 + 1_638_400) + 262_144_000
    assert flops.token_flops(MIXTRAL, 100, True) == 1_842_610_176


def test_mixtral_prefill_token():
    # a prompt token at context 2048, no head: per layer projections
    # 83,886,080, router 65,536, two experts 704,643,072, scores over 2048
    # keys 4*32*128*2048 = 33,554,432; two layers.
    assert flops.token_flops(MIXTRAL, 2048, False) == \
        2 * (83_886_080 + 65_536 + 704_643_072 + 33_554_432)
    assert flops.token_flops(MIXTRAL, 2048, False) == 1_644_298_240


def test_chunk_counts_causal_keys():
    # 4 tokens from position 0 attend over 1+2+3+4 = 10 keys in all
    per_token = flops.token_flops(MIXTRAL, 0, False)
    assert flops.chunk_flops(MIXTRAL, [(0, 4)]) == \
        4 * per_token + 2 * 4 * 32 * 128 * 10
    assert flops.chunk_flops(MIXTRAL, [(0, 4)]) == 6_309_085_184


def test_decode_step_sums_rows():
    assert flops.decode_step_flops(MIXTRAL, [5, 7]) == \
        flops.token_flops(MIXTRAL, 5, True) + \
        flops.token_flops(MIXTRAL, 7, True)


def test_moe_gemm_one_token():
    # one token: 2 routed rows, exactly 2 distinct experts of 3*4096*14336
    # bf16 weights, 2 rows of 4096 read and written
    f, b = flops.moe_gemm_cost(MIXTRAL, 1)
    assert f == 704_643_072
    assert b == 2 * 3 * 4096 * 14336 * 2 + 2 * 2 * 4096 * 2 == 704_675_840


def test_distinct_experts_saturate():
    assert flops.distinct_experts(MIXTRAL, 1) == pytest.approx(2.0)
    assert flops.distinct_experts(MIXTRAL, 10_000) == pytest.approx(8.0)
    assert flops.distinct_experts(MIXTRAL, 16) == \
        pytest.approx(8 * (1 - 0.75 ** 16))


def test_decode_attention_reads_real_context():
    f, b = flops.decode_attention_cost(MIXTRAL, [100, 50])
    assert f == 4 * 32 * 128 * 150 == 2_457_600
    # 150 positions of k and v, 8 heads of 128, bf16; q in and out
    assert b == 150 * 2 * 8 * 128 * 2 + 2 * 2 * 32 * 128 * 2 == 647_168


def test_flash_attention_chunk():
    # 2 tokens at positions 10, 11 attend over 11 + 12 = 23 keys; they
    # read 12 positions of k/v and write 2 rows
    f, b = flops.flash_attention_cost(MIXTRAL, [(10, 2)])
    assert f == 4 * 32 * 128 * 23 == 376_832
    assert b == 12 * 2 * 8 * 128 * 2 + 2 * 2 * 32 * 128 * 2 == 81_920


def test_least_time_takes_the_binding_roof():
    assert flops.least_time(197e12, 0.0, PEAKS) == pytest.approx(1.0)
    assert flops.least_time(0.0, 819e9, PEAKS) == pytest.approx(1.0)
    f, b = flops.moe_gemm_cost(MIXTRAL, 1)     # decode: bandwidth-bound
    assert flops.least_time(f, b, PEAKS) == pytest.approx(b / 819e9)
