"""The configurations' operation and byte counts against hand counts."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402


def cfg_and_ref(name):
    doc = common.load_json(common.BENCH / "configs" / f"{name}.json")
    return doc["model"], common.reference_module(doc)


def test_hymba_matmul_parameters_by_hand():
    cfg, ref = cfg_and_ref("hymba-1.5b-L8")
    attn = 1600 * 1600 * 2 + 1600 * 320 * 2
    ssm = 1600 * 1600 * 3 + 1600 * 16 * 2 + 1600 * 25
    mlp = 3 * 1600 * 5504
    assert ref.matmul_params(cfg) == 8 * (attn + ssm + mlp) + 1600 * 32001
    assert ref.matmul_params(cfg) == 373_876_800


def test_hymba_train_flops_per_token_by_hand():
    cfg, ref = cfg_and_ref("hymba-1.5b-L8")
    # a query at position p sees min(p + 1, 1024) keys
    keys = (sum(range(1, 1025)) + 1024 * 1024) / 2048
    assert ref.mean_keys(2048, 1024) == pytest.approx(keys) == 768.25
    fwd = 2 * 373_876_800 + 8 * (4 * 25 * 64 * 768.25 + 4 * 25 * 16 * 64)
    assert ref.train_flops_per_token(cfg, 2048) == pytest.approx(3 * fwd)
    assert ref.train_flops_per_token(cfg, 2048) == pytest.approx(2.3637e9,
                                                                 rel=1e-4)


def test_hymba_counts_agree_with_the_programs_parameter_count():
    common.add_program_to_path()
    from repro.configs.base import ModelConfig
    from repro.models.api import count_params
    cfg, ref = cfg_and_ref("hymba-1.5b-L8")
    vectors = 8 * (1600 * 3 + 25 * 3 + 4 * 1600) + 1600   # norms, ssm
    embed = 32001 * 1600
    assert count_params(ModelConfig(**cfg)) == \
        ref.matmul_params(cfg) + embed + vectors


def test_minicpm3_parameters_by_hand():
    cfg, ref = cfg_and_ref("minicpm3-4b")
    attn = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 128
            + 40 * 64 * 2560)
    layer = attn + 3 * 2560 * 6400
    assert ref.matmul_params(cfg) == 62 * layer + 2560 * 73448
    assert ref.all_params(cfg) == ref.matmul_params(cfg) \
        + 62 * (2 * 2560 + 768 + 256) + 2560
    assert ref.all_params(cfg) == 4_073_875_968


def test_minicpm3_agrees_with_the_programs_parameter_count():
    common.add_program_to_path()
    from repro.configs.base import ModelConfig
    from repro.models.api import count_params
    cfg, ref = cfg_and_ref("minicpm3-4b")
    assert count_params(ModelConfig(**cfg)) == ref.all_params(cfg)


def test_minicpm3_decode_counts_by_hand():
    cfg, ref = cfg_and_ref("minicpm3-4b")
    rows, tokens = 1000, 4
    assert ref.decode_bytes(cfg, rows) == \
        2 * 4_073_875_968 + 2 * 62 * (256 + 32) * rows
    assert ref.decode_flops(cfg, rows, tokens) == \
        2 * ref.matmul_params(cfg) * tokens + 62 * 40 * (4 * 256 + 2 * 32) \
        * rows


def test_the_peaks_table_is_keyed_by_device_kind_and_refuses_others():
    assert common.peaks("TPU v5 lite")["bf16_flops"] == 1.97e14
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")
    assert "cloud.google.com" in json.loads(
        (common.BENCH / "peaks.json").read_text())["source"]
