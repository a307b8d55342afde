"""Operations and bytes an SDAR-MoE training step by block diffusion
*requires*, from the shapes held here and the rows the router sent here.
The counting is ``laguna_flops.py``'s (2 FLOPs a multiply-add, backward
twice the forward, recomputation and element-wise work, the per-head q/k
norms among it, not counted; 18 x hidden x expert width FLOPs a routed row
the program counted). ``batch`` and ``seq`` are of *data* tokens; what the
objective requires for them:

- the layers' matrices (q, k, v, o of the heads held; the router at its
  published width) over both copies, 2 x seq positions a sequence;
- attention over the live pairs of the block-diffusion mask, counted from
  its definition and not from tiles. With n = seq / block blocks of B: a
  noisy query sees its own block, seq x B pairs; the clean blocks before
  it, B^2 n (n - 1) / 2; a clean query the clean blocks through its own,
  B^2 n (n + 1) / 2. Together seq^2 + seq x B of the (2 seq)^2. Both
  products for each *query* head; K and V bytes once for each *KV* head,
  q, k, v, o and their gradients over 2 x seq positions;
- the head over the noisy copy alone, seq positions: the clean copy
  carries no loss.
"""

from benchmark.laguna_flops import (  # noqa: F401  (the family's counts)
    expert_flops_per_row,
    grouped_matmul_bytes,
)


def layer_shapes(cfg):
    """One dict for each layer kept: ``heads``, ``kv_heads``, ``window``,
    ``sparse``."""
    return [{"heads": cfg["num_attention_heads"],
             "kv_heads": cfg["num_key_value_heads"],
             "window": None, "sparse": True} for _ in cfg["layer_types"]]


def live_pairs(seq, block):
    """Query-key pairs of one head over the two-copy stream of ``seq``
    data positions, summed from the definition block by block."""
    n = seq // block
    own = n * block * block
    noisy_on_clean = sum(block * k * block for k in range(n))
    clean_on_clean = sum(block * (k + 1) * block for k in range(n))
    return own + noisy_on_clean + clean_on_clean


def layer_matmul_params(cfg):
    """Parameters outside the routed experts that multiply every position
    of the stream, all layers together."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    return sum(D * hd * 2 * (layer["heads"] + layer["kv_heads"])
               + D * cfg["num_experts_published"]
               for layer in layer_shapes(cfg))


def train_attention_flops_per_step(cfg, batch, seq):
    pairs = live_pairs(seq, cfg["block_length"])
    return 3 * batch * sum(4 * cfg["head_dim"] * layer["heads"] * pairs
                           for layer in layer_shapes(cfg))


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv: six tensors of the query heads' size and six of
    the KV heads' size for each layer, over 2 x seq positions."""
    return itemsize * sum(
        6 * batch * 2 * seq * cfg["head_dim"]
        * (layer["heads"] + layer["kv_heads"])
        for layer in layer_shapes(cfg))


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Required FLOPs of one optimizer step; ``routed_rows`` is the
    program's count of assignments that landed on experts held here in
    the step (all layers, both copies)."""
    return (6 * layer_matmul_params(cfg) * batch * 2 * seq
            + 6 * cfg["hidden_size"] * cfg["vocab_size"] * batch * seq
            + expert_flops_per_row(cfg) * routed_rows
            + train_attention_flops_per_step(cfg, batch, seq))
