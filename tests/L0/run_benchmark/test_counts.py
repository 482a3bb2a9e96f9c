"""The FLOP and byte functions against hand counts for BERT-Large and
GPT-2-medium."""

import json
import os

import pytest

from benchmark.harness import load_module

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")


def kernel(name):
    return load_module("kernels", name, BENCH)


def sizes(config):
    ref = load_module("reference", config, BENCH)
    return ref.sizes_of(json.load(open(
        os.path.join(BENCH, "configs", config + ".json"))))


def test_bert_large_training_flops_per_token():
    sz = sizes("bert_large")
    flops = kernel("train_flops")
    # 24 layers x 12 h^2, the MLM transform h^2, the tied decoder V x h
    assert flops.matmul_params(sz) == 24 * 12 * 1024 ** 2 + 1024 ** 2 \
        + 30522 * 1024 == 334_292_992
    per_token = flops.flops_per_token(sz, 128)
    assert per_token == 6 * 334_292_992 + 12 * 24 * 128 * 1024
    assert per_token == pytest.approx(2.04e9, rel=0.005)


def test_layer_norm_bytes_are_the_minimal_streams():
    need = kernel("fused_layer_norm").bytes_needed(8192, 1024, act_bytes=2)
    assert need["fwd"] == 2 * 8192 * 1024 * 2 + 2 * 1024 * 4 + 2 * 8192 * 4
    assert need["bwd"] == 3 * 8192 * 1024 * 2 + 3 * 1024 * 4 + 2 * 8192 * 4
    # five activation streams in all and nothing hidden beside them: the
    # parameters and statistics add under a quarter of a percent
    streams = 5 * 8192 * 1024 * 2
    assert streams < need["fwd"] + need["bwd"] < 1.0025 * streams


def test_xentropy_bytes_read_the_logits_once_each_way():
    need = kernel("xentropy").bytes_needed(8192, 30522, logit_bytes=4)
    logits = 8192 * 30522 * 4
    assert need["fwd"] == logits + 8192 * 12
    assert need["bwd"] == 2 * logits + 8192 * 12


def test_gpt2_medium_decode_bytes():
    sz = sizes("gpt2_medium")
    dec = kernel("decode_step")
    matrices = 24 * 12 * 1024 ** 2 + 50304 * 1024
    assert dec.weight_bytes(sz) == 2 * (matrices + 24 * 9 * 1024) \
        + 4 * 49 * 2 * 1024
    assert dec.weight_bytes(sz) == pytest.approx(0.708e9, rel=0.005)
    # 96 KiB of K and V per cached token
    assert dec.kv_bytes(sz, 1) == 2 * 24 * 1024 * 2 == 96 * 1024
    assert dec.bytes_needed(sz, 1000) == dec.weight_bytes(sz) + 1000 * 98304
