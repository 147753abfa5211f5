"""The shape-based counts against hand-worked numbers."""
import json

import pytest

from bench.lib import counts, spec


def _cell(name):
    return spec.resolve(spec.load_benchmark(), name)


def test_matmul_params_deepseek_7b_two_layers():
    c = _cell("train.ds7b.s1024").config
    # per layer: attention 4 * 4096^2, MLP 3 * 4096 * 11008; head 4096 * 12800
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert counts.matmul_params(c) == 2 * per_layer + 4096 * 12800


def test_train_flops_train_ds7b_s1024_is_23_3_tflop():
    cell = _cell("train.ds7b.s1024")
    t = cell.traffic
    flops = counts.train_flops_per_step(cell.config, t["global_batch"],
                                        t["seq"])
    # 6 N T + 12 L H hd S T with N = 457,179,136 and T = 8 * 1024
    hand = 8192 * (6 * 457_179_136 + 12 * 2 * 32 * 128 * 1024)
    assert flops == hand
    assert flops == pytest.approx(23.3e12, rel=2e-3)


def test_train_flops_gqa_counts_kv_heads():
    # DeepSeek LLM 67B widths (8 KV heads) at 2 layers and 1/8 vocabulary
    c = {"hidden_size": 8192, "num_attention_heads": 64,
         "num_key_value_heads": 8, "intermediate_size": 22016,
         "num_hidden_layers": 2, "vocab_size": 12800}
    attn = 2 * 8192 * 64 * 128 + 2 * 8192 * 8 * 128
    per_layer = attn + 3 * 8192 * 22016
    assert counts.matmul_params(c) == 2 * per_layer + 8192 * 12800
    assert counts.train_flops_per_step(c, 16, 1024) == pytest.approx(
        149.67e12, rel=1e-3)


def test_vb_scatter_bytes_train_ds7b_s1024():
    c = _cell("train.ds7b.s1024").config
    x1 = 8 * 1024 * 4096 * 4
    assert counts.vb_scatter_bytes_per_step(c, 8, 1024) == \
        2 * (x1 + 8 * 1024 * 4) + 2 * x1


def test_peak_table_refuses_unknown_device():
    from bench.lib.peaks import peak_for
    assert peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peak_for("TPU v9 imaginary")


def test_config_files_are_json_objects():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        data = json.loads((spec.ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in data["published"]
            assert data[key] != data["published"][key]
