"""lib/counts.py against hand-worked numbers."""

import json
import pathlib

import pytest

from lib import counts, peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_bert_large_step_is_16_3_tflop():
    cfg = config("bert_large_o2")
    # 12 H^2 = 12.58 M matmul weights a layer, 302 M in 24 layers
    assert counts.layer_matmul_params(cfg, gated=False) * 24 == 301_989_888
    blocks = 6 * 301_989_888 * 8192                  # 14.84 T
    attention = 3 * 24 * 4 * 16 * 512 * 512 * 1024   # 1.24 T
    head = 3 * 2 * 16 * 80 * (1024 * 1024 + 1024 * 30528)   # 0.25 T
    got = counts.bert_train_step_flops(cfg, 16, 512, 80, 30528)
    assert got == blocks + attention + head
    assert got / 1e12 == pytest.approx(16.33, abs=0.01)


def test_mistral_l8_kv_is_32_kib_a_token():
    assert counts.kv_bytes_per_token(config("mistral_7b_l8")) == 32768


def test_mistral_layer_weights():
    cfg = config("mistral_7b_l8")
    # qkv 4096 x 6144, out 4096 x 4096, three 4096 x 14336
    assert counts.layer_matmul_params(cfg, gated=True) == (
        4096 * 6144 + 4096 * 4096 + 3 * 4096 * 14336)


def test_decoder_forward_counts_head_only_where_sampled():
    cfg = config("mistral_7b_l8")
    base = counts.decoder_forward_flops(cfg, 10, 0, 0)
    assert counts.decoder_forward_flops(cfg, 10, 0, 3) - base == \
        3 * 2 * 4096 * 32000
    assert counts.decoder_forward_flops(cfg, 10, 7, 0) - base == \
        8 * 4 * 32 * 128 * 7


def test_flash_counts_and_which_side_binds():
    ops, nbytes = counts.flash_fwd(16, 16, 512, 64)
    assert ops == 4 * 16 * 512 * 512 * 1024
    assert nbytes == 4 * 16 * 512 * 1024 * 2
    ops_b, bytes_b = counts.flash_bwd(16, 16, 512, 64)
    assert ops_b == 2.5 * ops and bytes_b == 2 * nbytes
    p = peaks.peaks_for("TPU v5 lite")
    t, side = counts.least_seconds(ops, nbytes, p)
    assert side == "compute" and t == pytest.approx(ops / 197e12)
    # decode attention over live pages is bound by the bytes
    ops_d, bytes_d = counts.paged_decode(config("mistral_7b_l8"), 16 * 200, 16)
    assert bytes_d == (2 * 8 * 128 * (3200 + 16) + 2 * 32 * 128 * 16) * 2
    assert counts.least_seconds(ops_d, bytes_d, p)[1] == "memory"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")


def test_kernel_roofline_reads_both_sources_of_counts():
    import numpy as np

    import run

    reader = run.load_module("readers", "kernel_roofline")
    pk = peaks.peaks_for("TPU v5 lite")
    cfg = config("mistral_7b_l8")
    ops = {"a": {"label": "%paged_decode_fused.1 tpu_custom_call",
                 "count": 16, "total_s": 0.16},
           "b": {"label": "%fusion.2", "count": 9, "total_s": 9.0}}
    base = {"peaks": pk, "trace": {"ops": ops}, "config": cfg,
            "traced": (1.0, 2.0)}
    # two decode rows arrived inside the traced second, contexts 100 and
    # 300: each layer reads 2 x 8 x 128 x 2 B of K and V a live token,
    # 402 of them with the two new rows, and 2 x 32 x 128 x 2 B of q, o
    nbytes = 2 * 8 * 128 * 402 * 2 + 2 * 32 * 128 * 2 * 2
    facts = {"decode_t": np.array([0.5, 1.2, 1.9, 2.5]),
             "decode_ctx": np.array([50, 100, 300, 70])}
    args = {"patterns": ["%paged_decode_fused.", "tpu_custom_call"],
            "traced_rows": "paged_decode", "times": "decode_t",
            "contexts": "decode_ctx"}
    got = reader.read(args, dict(base, facts=facts))
    assert got == pytest.approx(100 * 8 * (nbytes / 819e9) / 0.16)
    # per step: 16 events of one instruction are 16 steps of 3 layers
    shape = dict(batch=16, heads=16, seq=512, d=64, layers=3)
    args = {"patterns": ["%paged_decode_fused."], "shape": "attention",
            "per_step": ["flash_fwd"]}
    t = counts.least_seconds(*counts.flash_fwd(16, 16, 512, 64), pk)[0]
    got = reader.read(args, dict(base, facts={"attention": shape}))
    assert got == pytest.approx(100 * t * 3 * 16 / 0.16)
    # nothing to read: nothing, never 0
    assert reader.read(dict(args, patterns=["no such"]),
                       dict(base, facts={"attention": shape})) is None
    assert reader.read(args, dict(base, peaks=None, facts={})) is None
