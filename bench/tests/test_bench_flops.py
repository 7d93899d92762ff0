"""FLOP and byte counts for yi-6b-8l against numbers worked by hand."""

from __future__ import annotations

import json
from pathlib import Path

from bench import flops

M = json.loads((Path(__file__).resolve().parents[1] / "configs"
                / "yi-6b-8l.json").read_text())["model"]

# Per layer: q 4096x4096, k and v 4096x512 each, o 4096x4096, MLP
# 3 x 4096 x 11008 = 172,965,888; 8 layers, plus the 4096 x 64,000 head.
MATMUL = 8 * (16_777_216 + 2 * 2_097_152 + 16_777_216 + 135_266_304) \
    + 262_144_000


def test_parameter_counts():
    assert flops.matmul_params(M) == MATMUL == 1_646_264_320
    # + the 64,000 x 4096 embedding and 17 RMSNorm vectors of 4096
    assert flops.param_count(M) == MATMUL + 262_144_000 + 17 * 4096
    assert flops.param_count(M) == 1_908_477_952


def test_kv_and_token_counts():
    assert flops.kv_bytes_per_token(M) == 8 * 2 * 4 * 128 * 2 == 16_384
    # one token at context 1,000: 2 per weight, 4 x 8 x 32 x 128 per position
    assert flops.token_flops(M, 1000) == 2 * MATMUL + 131_072 * 1000
    # a 3-token prompt attends to 1 + 2 + 3 positions
    assert flops.prefill_flops(M, 3) == 3 * 2 * MATMUL + 131_072 * 6


def test_decode_bytes_count_live_tokens_only():
    weights = (MATMUL + 17 * 4096) * 2
    assert flops.decode_bytes(M, []) == weights
    two = flops.decode_bytes(M, [100, 2000])
    assert two == weights + 2 * 4096 * 2 + 16_384 * 2100
