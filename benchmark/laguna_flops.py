"""Operations and bytes a Laguna training step *requires*, from the shapes
held here and the rows the router sent here. As ``flops.py`` counts: 2
FLOPs a multiply-add, backward twice the forward, recomputation and
element-wise work not counted, so a share of a peak computed from these
stays under 100%.

- Matmuls outside the routed experts: every parameter that multiplies each
  token: q, k, v, o and gate projections of the heads held, the leading
  dense MLP whole, the shared expert, the router at its published width,
  the head over the vocabulary slice. 6 FLOPs a parameter a token.
- Routed experts: a row is one token on one expert held here. The count of
  rows is the program's own (``smp_moe_local_assignments``), not the
  expectation tokens x top_k x held / experts: 18 x hidden x expert width
  FLOPs a row (three matrices, forward and backward).
- Attention: query-key pairs inside the causal band. A full layer has
  T (T + 1) / 2 pairs a head; a window layer has W (W + 1) / 2 for its
  first W queries and W for each one after (T (T + 1) / 2 if T <= W).
  Counting the full triangle in a window layer would read sixteen times
  too much at T 8,192, W 512. Both products (QK^T, PV) for each *query*
  head; K and V bytes once for each *KV* head.
"""


def window_pairs(seq, window):
    """Query-key pairs of one head over ``seq`` positions when query i
    sees keys j with 0 <= i - j < window (``None``: every earlier key)."""
    if window is None or seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_shapes(cfg):
    """One dict for each layer kept: ``heads``, ``kv_heads``, ``window``,
    ``sparse``."""
    n = len(cfg["layer_types"])
    dense = {i for i, t in enumerate(cfg["mlp_layer_types"][:n])
             if t == "dense"}
    return [{
        "heads": cfg["num_attention_heads_per_layer"][i],
        "kv_heads": cfg["num_key_value_heads"],
        "window": cfg["sliding_window"]
        if cfg["layer_types"][i] == "sliding_attention" else None,
        "sparse": i not in dense,
    } for i in range(n)]


def dense_matmul_params(cfg):
    """Parameters that multiply every token (routed experts left out)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    total = D * cfg["vocab_size"]                       # the head
    for layer in layer_shapes(cfg):
        H, Hkv = layer["heads"], layer["kv_heads"]
        total += D * hd * (2 * H + 2 * Hkv) + D * H     # q, o, k, v, gate
        if layer["sparse"]:
            total += D * cfg["num_experts_published"]   # router
            total += 3 * D * cfg["shared_expert_intermediate_size"]
        else:
            total += 3 * D * cfg["intermediate_size"]
    return total


def expert_flops_per_row(cfg):
    """Forward + backward FLOPs of one routed row through one expert."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_forward_flops(cfg, seq):
    hd = cfg["head_dim"]
    return sum(4 * hd * layer["heads"] * window_pairs(seq, layer["window"])
               for layer in layer_shapes(cfg))


def train_attention_flops_per_step(cfg, batch, seq):
    return 3 * attention_forward_flops(cfg, seq) * batch


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv: six tensors of the query heads' size and six of
    the KV heads' size for each layer."""
    hd = cfg["head_dim"]
    return sum(6 * batch * seq * hd * (layer["heads"] + layer["kv_heads"])
               for layer in layer_shapes(cfg)) * itemsize


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Required FLOPs of one optimizer step; ``routed_rows`` is the
    program's count of assignments that landed on experts held here in
    the step (all expert layers together)."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + expert_flops_per_row(cfg) * routed_rows
            + train_attention_flops_per_step(cfg, batch, seq))


def grouped_matmul_bytes(cfg, routed_rows, expert_layer_calls, itemsize=2):
    """Least HBM traffic of the routed experts' grouped products over
    ``expert_layer_calls`` (layers x microbatches x steps) calls that
    together moved ``routed_rows`` rows: forward reads the rows and the
    held experts' three matrices and writes the rows' outputs; backward
    reads rows, output gradients and matrices and writes row gradients and
    the matrices' gradients."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    matrices = cfg["num_experts"] * 3 * D * F
    return itemsize * (5 * routed_rows * D
                       + 3 * matrices * expert_layer_calls)
