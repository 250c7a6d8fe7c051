"""``benchmark/arith.py`` and the llama-like family's arithmetic
against numbers worked by hand."""

import pytest

from benchmark import arith, generate
from benchmark.families import llama_like, mistral, qwen2
from benchmark.run import ROOT

QWEN, _ = generate.load_config(
    f"{ROOT}/benchmark/configs/qwen2.5-0.5b.json")
MISTRAL, _ = generate.load_config(
    f"{ROOT}/benchmark/configs/mistral-7b-v0.3-l4.json")


def test_qwen_parameter_count():
    # a layer: q 896*896 + k, v 2*896*128 + o 896*896 = 1,835,008;
    # biases 896 + 128 + 128 = 1,152; MLP 3*896*4864 = 13,074,432;
    # two norms 1,792 -> 14,912,384. 24 layers = 357,897,216; tied
    # embedding 151936*896 = 136,134,656; final norm 896.
    assert qwen2.n_params(QWEN) == 357_897_216 + 136_134_656 + 896
    assert qwen2.n_params(QWEN) == 494_032_768  # "0.5B": 494M


def test_mistral_parameter_count():
    # the published 32 layers make the 7.25B of Mistral-7B-v0.3
    assert mistral.n_params(dict(MISTRAL, num_hidden_layers=32)) \
        == 7_248_023_552
    # a layer is 218,112,000; 4 layers + untied embedding and head
    # (2 * 32768 * 4096) + final norm
    assert mistral.n_params(MISTRAL) == 4 * 218_112_000 \
        + 268_435_456 + 4096


def test_qwen_flops_of_one_token():
    # a layer: qkv 2*896*(14+2*2)*64 = 2,064,384; o 2*14*64*896 =
    # 1,605,632; attention over itself 2*1*14*64 = 1,792; MLP
    # 2*896*4864*3 = 26,148,864 -> 29,820,672. Head 2*896*151936.
    assert qwen2.forward_flops(QWEN, [1]) \
        == 24 * 29_820_672 + 272_269_312 == 987_965_440
    assert arith.train_flops(qwen2, QWEN, [1]) == 3 * 987_965_440


def test_attention_grows_with_the_square_of_a_sequence():
    one = qwen2.forward_flops(QWEN, [512])
    two = qwen2.forward_flops(QWEN, [256, 256])
    # the same tokens in two sequences: only the causal square halves
    assert one - two == 24 * 2 * 14 * 64 * (512 ** 2 - 2 * 256 ** 2)


@pytest.mark.parametrize("hf,seqlens,share", [
    (QWEN, [512] * 128, 0.27), (MISTRAL, [512] * 128, 0.13)])
def test_head_share(hf, seqlens, share):
    assert llama_like.head_share(hf, seqlens) \
        == pytest.approx(share, abs=0.01)


def test_decode_bytes_of_one_step():
    # one token step of 128 sequences with 256 tokens behind them:
    # the bf16 weights once, 988,065,536 bytes, and 128*256 prefixes
    # of 2 (k, v) * 24 layers * 2 heads * 64 * 2 bytes = 12,288
    assert qwen2.kv_bytes_per_token(QWEN) == 12_288
    assert qwen2.decode_bytes(QWEN, 128, 256, 1) \
        == 988_065_536 + 128 * 256 * 12_288
    # four replicas each read their own copy of the weights
    assert qwen2.decode_bytes(QWEN, 128, 256, 1, replicas=4) \
        - qwen2.decode_bytes(QWEN, 128, 256, 1) == 3 * 988_065_536
    # the prefix grows by one token a step
    assert qwen2.decode_bytes(QWEN, 1, 10, 3) \
        == 3 * 988_065_536 + (10 + 11 + 12) * 12_288


def test_unknown_device_is_an_error():
    assert arith.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        arith.peaks("cpu")
