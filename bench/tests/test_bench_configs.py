"""Each configuration file of the benchmark is the program's own preset of
that model, cut only where its ``reduced`` keys say, and those keys hold the
published values under ``published``."""
import json

import pytest

from bench.lib import spec
from bench.lib.weights import model_config

# Hugging Face key -> the program's ModelConfig field it sets
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}
SHAPE = ("arch_type", "attention", "rope", "resolved_head_dim")

ENTRIES = {c["name"]: c for c in spec.load_benchmark()["configs"]}


def _fields(cfg):
    return {f: getattr(cfg, f) for f in (*FIELDS.values(), *SHAPE)}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_config_file_is_the_preset_cut_as_reduced_says(name):
    from repro.configs import get_config
    entry = ENTRIES[name]
    c = json.loads((spec.ROOT / entry["file"]).read_text())
    assert set(c["published"]) == set(entry["reduced"])
    preset = _fields(get_config(name))
    published = _fields(model_config(dict(c, **c["published"]), name))
    assert published == preset
    run = _fields(model_config(c, name))
    changed = {f for f in run if run[f] != preset[f]}
    assert changed == {FIELDS[k] for k in entry["reduced"]}
