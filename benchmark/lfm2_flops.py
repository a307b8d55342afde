"""Operations and bytes an LFM2-MoE training step *requires*, from the
shapes held here and the rows the router sent here. The counting is
``laguna_flops.py``'s (2 FLOPs a multiply-add, backward twice the forward,
recomputation and element-wise work, the gates, the taps and the q/k norms
among it, not counted; attention over the causal triangle, both products
for each *query* head, K and V bytes once for each *KV* head; 18 x hidden x
expert width FLOPs a routed row the program counted). What differs is
counted here: a layer's mixer is the short convolution (its input
projection to three streams and its output projection, 4 D^2 parameters
that multiply every token) or attention (q, k, v and o of the heads held);
its feed-forward the leading dense MLP whole or the router at its
published width; the head is the input table over the vocabulary slice,
tied, counted once as the product it is.

``conv_core_bytes_per_step``: the bytes the gate-conv-gate stage of every
convolution mixer must move in a step, forward and backward with no
recomputation counted, whatever implements it
(``nn/conv.conv_core_bytes`` is the program's own count of one call, and a
test holds the two equal): eleven [tokens, hidden] bf16 tensors a layer.
"""

from benchmark import laguna_flops
from benchmark.laguna_flops import (  # noqa: F401  (the family's counts)
    expert_flops_per_row,
    grouped_matmul_bytes,
)


def layer_shapes(cfg):
    """One dict for each layer kept: ``conv`` (the mixer), ``heads``,
    ``kv_heads`` (0 in a convolution layer), ``window`` (none here),
    ``sparse``."""
    out = []
    for i, kind in enumerate(cfg["layer_types"]):
        conv = kind == "conv"
        out.append({
            "conv": conv,
            "heads": 0 if conv else cfg["num_attention_heads"],
            "kv_heads": 0 if conv else cfg["num_key_value_heads"],
            "window": None,
            "sparse": i >= cfg["num_dense_layers"],
        })
    return out


def dense_matmul_params(cfg):
    """Parameters that multiply every token (routed experts left out)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    total = D * cfg["vocab_size"]                       # the tied head
    for layer in layer_shapes(cfg):
        if layer["conv"]:
            total += 4 * D * D                          # in_proj, out_proj
        else:
            total += D * hd * 2 * (layer["heads"] + layer["kv_heads"])
        if layer["sparse"]:
            total += D * cfg["num_experts_published"]   # the router
        else:
            total += 3 * D * cfg["intermediate_size"]
    return total


def train_attention_flops_per_step(cfg, batch, seq):
    pairs = laguna_flops.window_pairs(seq, None)
    return 3 * batch * sum(4 * cfg["head_dim"] * layer["heads"] * pairs
                           for layer in layer_shapes(cfg))


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Six tensors of the query heads' size and six of the KV heads' size
    for each attention layer (``laguna_flops``)."""
    return itemsize * sum(
        6 * batch * seq * cfg["head_dim"] * (layer["heads"]
                                             + layer["kv_heads"])
        for layer in layer_shapes(cfg))


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Required FLOPs of one optimizer step; ``routed_rows`` is the
    program's count of assignments that landed on experts held here in
    the step (all expert layers together)."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + expert_flops_per_row(cfg) * routed_rows
            + train_attention_flops_per_step(cfg, batch, seq))


def conv_core_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward: B, C, u read and the gated output written; backward:
    those three and the output's gradient read, three gradients written."""
    mixers = sum(layer["conv"] for layer in layer_shapes(cfg))
    return (4 + 7) * mixers * batch * seq * cfg["hidden_size"] * itemsize
