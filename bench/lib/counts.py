"""Operations and bytes that the algorithm needs, worked out from shapes.

These are the yardstick's own numbers: what a step or a kernel call must do
at the least, never what the compiled program happens to do (recompute,
padding and copies are left out).  Configurations are the JSON files under
``bench/configs`` (Hugging Face key names).
"""
from __future__ import annotations


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    KV = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // H
    return {"d": d, "H": H, "KV": KV, "hd": hd, "ff": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"]}


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies: attention projections, the
    SwiGLU MLP and the output head (the embedding is a lookup)."""
    k = dims(c)
    attn = k["d"] * k["H"] * k["hd"] * 2 + k["d"] * k["KV"] * k["hd"] * 2
    mlp = 3 * k["d"] * k["ff"]
    return k["L"] * (attn + mlp) + k["d"] * k["V"]


def train_flops_per_step(c: dict, batch: int, seq: int) -> float:
    """Forward plus backward FLOPs of one training step, recompute not
    counted: 6·N per token for the weights and 12·L·H·hd·S per token for
    attention (scores and values, forward and backward, over the full
    sequence as in the PaLM MFU count)."""
    k = dims(c)
    per_token = (6 * matmul_params(c)
                 + 12 * k["L"] * k["H"] * k["hd"] * seq)
    return float(per_token * batch * seq)


def vb_scatter_bytes_per_step(c: dict, batch: int, seq: int,
                              act_itemsize: int = 4) -> float:
    """HBM bytes of one step's virtual-batch reassembly: the forward pass
    scatters X^(1) (batch, seq, d) and the targets (batch, seq) int32, the
    backward pass gathers the cotangent of X^(1); each row is read once and
    written once."""
    d = c["hidden_size"]
    x1 = batch * seq * d * act_itemsize
    tgt = batch * seq * 4
    return float(2 * (x1 + tgt) + 2 * x1)
