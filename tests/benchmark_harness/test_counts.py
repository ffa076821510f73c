"""Counts, rooflines, the peak table, and the characters of names."""

import glob
import os
import re
import subprocess
import sys

import pytest

from benchmark import counts, harness, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
V5E = peaks.PEAKS["TPU v5 lite"]


def test_bert_large_count_is_about_two_gflop_a_token():
    f = counts.encoder_train_flops_per_token(
        hidden=1024, inner=4096, layers=24, seq=512, vocab=30522,
        mask_rate=0.15)
    blocks = 24 * (2 * 12 * 1024 * 1024 + 4 * 512 * 1024)
    head = 0.15 * (2 * 1024 * 1024 + 2 * 1024 * 30522)
    assert f == pytest.approx(3 * (blocks + head), rel=1e-4)
    assert 1.9e9 < f < 2.1e9


def test_head_counts_masked_positions_only():
    kw = dict(hidden=1024, inner=4096, layers=24, seq=512, vocab=30522)
    some = counts.encoder_train_flops_per_token(mask_rate=0.15, **kw)
    every = counts.encoder_train_flops_per_token(mask_rate=1.0, **kw)
    assert every - some == pytest.approx(
        3 * 0.85 * (2 * 1024 * 1024 + 2 * 1024 * 30522))


def test_decoder_counts():
    kw = dict(hidden=2048, inner=8192, layers=24, vocab=50257)
    one = counts.decoder_forward_flops(new_tokens=1, context=100,
                                       heads_out=1, **kw)
    assert one == 24 * (2 * 12 * 2048 ** 2 + 4 * 100 * 2048) + \
        2 * 2048 * 50257
    assert counts.decoder_train_flops_per_token(seq=2048, **kw) == \
        3 * counts.decoder_forward_flops(new_tokens=1, context=1024,
                                         heads_out=1, **kw)


def test_dims_in_either_naming():
    a = counts.dims({"hidden_size": 8, "intermediate_size": 32,
                     "num_hidden_layers": 2, "num_attention_heads": 2,
                     "vocab_size": 11})
    b = counts.dims({"n_embd": 8, "n_inner": 32, "n_layer": 2, "n_head": 2,
                     "vocab_size": 11})
    assert a == b == {"hidden": 8, "inner": 32, "layers": 2, "heads": 2,
                      "vocab": 11}


@pytest.mark.parametrize("backward,matmuls", [(False, 2), (True, 5)])
def test_flash_call(backward, matmuls):
    f, b = counts.flash_call(batch=24, heads=16, seq_q=512, seq_k=512,
                             head_dim=64, causal=False, backward=backward)
    assert f == matmuls * 2 * 24 * 16 * 512 * 512 * 64
    t, bound = counts.roofline_seconds(f, b, V5E)
    assert bound == "compute" and t == f / 197e12
    half, _ = counts.flash_call(batch=24, heads=16, seq_q=512, seq_k=512,
                                head_dim=64, causal=True, backward=backward)
    assert half == f / 2


def test_paged_decode_is_bandwidth_bound():
    f, b = counts.paged_decode_call(context_tokens=16 * 800, heads=16,
                                    head_dim=128)
    assert b == 2 * 16 * 800 * 16 * 128 * 2 and f == 2 * b / 2
    t, bound = counts.roofline_seconds(f, b, V5E)
    assert bound == "bandwidth" and t == b / 819e9


def test_serve_flops_adds_prefill_and_decode():
    cfg = {"n_embd": 64, "n_inner": 256, "n_layer": 2, "n_head": 2,
           "vocab_size": 211}
    p = counts.serve_flops(cfg, [10], [])
    d = counts.serve_flops(cfg, [], [11, 12])
    assert counts.serve_flops(cfg, [10], [11, 12]) == p + d
    assert p > 0 and d > 0


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peak("TPU v9")
    assert peaks.peak("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_cpu_is_refused():
    import jax
    with pytest.raises(peaks.UnknownDevice, match="cpu"):
        peaks.require_chips(jax.devices(), 1)


def test_a_measurement_on_the_cpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-gpt2.chat", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--root", os.path.join(HERE, "data")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "UnknownDevice" in p.stderr
    assert '"correct"' not in p.stdout


class _Fake:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_too_few_chips_is_refused():
    with pytest.raises(peaks.UnknownDevice, match="4 chip"):
        peaks.require_chips([_Fake()], 4)
    assert peaks.require_chips([_Fake()], 1) is V5E


@pytest.mark.parametrize("root", [ROOT, os.path.join(HERE, "data")])
def test_names_units_and_files(root):
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = harness.load_cell(root, w["name"])
        assert cell.workload["runner"] in ("train", "serve")
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for _, spec in cell.per_layer:
            assert callable(harness.resolve(spec["reader"]))
    for path in glob.glob(os.path.join(root, "benchmark", "**", "*"),
                          recursive=True):
        rel = os.path.relpath(path, root)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
