"""Operations and bytes a Mellum training step *requires*, from the shapes
held here and the rows the router sent here. The counting is
``laguna_flops.py``'s (2 FLOPs a multiply-add, backward twice the forward,
recomputation and element-wise work, the per-head q/k norms among it, not
counted; attention over the causal band, both products for each *query*
head, K and V bytes once for each *KV* head; 18 x hidden x expert width
FLOPs a routed row the program counted), called on this family's
configuration seen as that file reads one (every layer the same head
count). What differs is counted here: the matmuls outside the routed
experts are the q, k, v and o projections of the heads held (no head
gate), the router at its published width (no shared expert; every layer
is routed) and the head over the vocabulary slice, 6 FLOPs a parameter a
token.
"""

from benchmark import laguna_flops
from benchmark.laguna_flops import (  # noqa: F401  (the family's counts)
    expert_flops_per_row,
    grouped_matmul_bytes,
)


def _as_laguna_reads(cfg):
    return dict(cfg, num_attention_heads_per_layer=[
        cfg["num_attention_heads"]] * len(cfg["layer_types"]))


def layer_shapes(cfg):
    """One dict for each layer kept: ``heads``, ``kv_heads``, ``window``,
    ``sparse``."""
    return laguna_flops.layer_shapes(_as_laguna_reads(cfg))


def dense_matmul_params(cfg):
    """Parameters that multiply every token (routed experts left out)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    total = D * cfg["vocab_size"]                       # the head
    for layer in layer_shapes(cfg):
        total += D * hd * 2 * (layer["heads"] + layer["kv_heads"])
        total += D * cfg["num_experts_published"]           # the router
    return total


def train_attention_flops_per_step(cfg, batch, seq):
    return laguna_flops.train_attention_flops_per_step(
        _as_laguna_reads(cfg), batch, seq)


def train_attention_bytes_per_step(cfg, batch, seq):
    return laguna_flops.train_attention_bytes_per_step(
        _as_laguna_reads(cfg), batch, seq)


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Required FLOPs of one optimizer step; ``routed_rows`` is the
    program's count of assignments that landed on experts held here in
    the step (all expert layers together)."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + expert_flops_per_row(cfg) * routed_rows
            + train_attention_flops_per_step(cfg, batch, seq))
