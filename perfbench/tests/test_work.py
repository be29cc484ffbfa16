"""The work counts against hand arithmetic for both configurations."""
import json
import os

import pytest

import small  # noqa: F401  (puts the harness on the path)
from harness import work

CONFIGS = os.path.join(small.ROOT, "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_danube_parameters_and_kv():
    c = cfg("h2o-danube-1.8b")
    # 24 x (2560 x (2560 + 2 x 640) + 2560 x 2560 + 3 x 2560 x 6912)
    # + 25 RMSNorm scales of 2560 + untied 2560 x 32000 head and embedding
    assert work.n_params(c) == 1_831_201_280
    k = work.counts(c)
    assert k.kv_bytes_per_token == 61_440       # 2 x 24 x 8 x 80 x 2 bytes
    assert k.matmul_flops == 2 * (1_667_235_840 + 81_920_000)
    assert k.weight_bytes == 2 * (1_667_235_840 + 125_440 + 81_920_000)


def test_delphi_parameters_and_kv():
    c = cfg("delphi-2m")
    # 12 x (4 x 120^2 + 2 x 120 x 480) blocks, 2 LayerNorms of 120 a block
    # and one final, MLP biases, 1,289 output biases, tied 1,289 x 120
    assert work.n_params(c) == 2_073_600 + 6_000 + 7_200 + 1_289 + 154_680
    k = work.counts(c)
    assert k.kv_bytes_per_token == 5_760        # 2 x 12 x 12 x 10 x 2 bytes
    assert k.flops_per_key == 4 * 12 * 10


@pytest.mark.parametrize("length", [1, 7, 256])
def test_prefill_is_tokens_plus_causal_attention(length):
    k = work.counts(cfg("h2o-danube-1.8b"))
    body = k.matmul_flops - 2 * k.head_weights
    assert k.prefill_flops(length) == (length * body + 2 * k.head_weights
                                       + k.flops_per_key
                                       * length * (length + 1) // 2)


def test_decode_tick_bytes():
    k = work.counts(cfg("h2o-danube-1.8b"))
    # 32 rows at 500 live keys each: weights once, KV over 16,000 tokens,
    # 32 embedding rows of bf16 and 32 float32 logit rows
    assert k.decode_bytes(1, 32, 16_000) == (
        k.weight_bytes + 61_440 * 16_000 + 32 * (2560 * 2 + 4 * 32_000))
