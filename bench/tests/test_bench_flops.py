"""Operation and byte counts against counts made by hand."""
import json
import os

import pytest

from bench import flops, weights

TINY = {"d": 8, "H": 2, "KV": 1, "hd": 4, "F": 16, "V": 10, "L": 3}


def test_flash_prefill_cost_by_hand():
    # B1 S4 H2 KV1 hd8: query i sees i+1 keys -> 10 pairs a head;
    # q.k and p.v are 2*hd each -> 4*hd*10*H; bytes: q, o at 2 heads and
    # k, v at 1 head, 4 rows of 8, 2 bytes each
    assert flops.flash_prefill_cost(1, 4, 2, 1, 8) == (640, 384)


def test_flash_decode_cost_by_hand():
    # B2, 5 live positions, H4 over KV2, hd16: 4*hd*live*H per row; bytes:
    # k and v of 5 positions at 2 heads, q and o at 4 heads, per row
    assert flops.flash_decode_cost(2, 5, 4, 2, 16) == (2560, 1792)


def test_step_flops_by_hand():
    # per layer 8*2*4 (q) + 2*8*1*4 (k, v) + 2*4*8 (o) + 3*8*16 (mlp) = 576
    assert flops.layer_matmul_params(TINY) == 576
    # prefill B1 S4: 2*3*576*4 + 3 layers * 320 (causal) + head 2*8*10
    assert flops.prefill_step_flops(TINY, 1, 4) == 13824 + 960 + 160
    # decode B2 at 5 live: 2*3*576*2 + 3 * 4*2*2*4*5 + head 2*8*10*2
    assert flops.decode_step_flops(TINY, 2, 5) == 6912 + 960 + 320


def test_internlm2_prefill_step_size():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "internlm2-1.8b.json")) as f:
        n = weights.dims(json.load(f))
    attn = n["L"] * flops.causal_attention_flops(8, 4096, n["H"], n["hd"])
    total = flops.prefill_step_flops(n, 8, 4096)
    assert attn == pytest.approx(13.2e12, rel=0.01)
    assert total == pytest.approx(112.3e12, rel=0.01)


def test_least_seconds_takes_the_binding_roof():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000, 10, peak) == 10.0
    assert flops.least_seconds(10, 1000, peak) == 100.0
