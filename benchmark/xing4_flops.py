"""Operations and bytes a Xing4.0 training step *requires*, from the shapes
held here and the rows the router sent here. The counting is
``laguna_flops.py``'s (2 FLOPs a multiply-add, backward twice the forward,
recomputation and element-wise work, the norms, the gates and the
Sinkhorn rounds among it, not counted; attention over the causal triangle;
18 x hidden x expert width FLOPs a routed row the program counted). What
differs is counted here: latent attention's five matrices (the two
down-projections whole, the up-projections and the output projection of the
heads held), a connection's coefficient product (n D x n (n + 2), twice a
layer), attention's two products at their own sizes (a pair costs 2 x 192
for the scores and 2 x 128 for the values), its bytes likewise (six tensors
of the query heads at 192 and six at 128: latent attention gives every head
its own key and value), the shared expert beside the router.

``mhc_bytes_per_step``: the bytes the hyper-connections' coefficient read
and two mixes must move in a step, forward and backward with no
recomputation counted, whatever implements them
(``nn/hyper_connection.mhc_bytes`` is the program's own count of one
sub-layer's call, and a test holds the two equal): 7 n + 5 [tokens, hidden]
bf16 tensors a sub-layer, 33 at four streams.
"""

from benchmark import laguna_flops


def layer_shapes(cfg):
    """One dict for each layer kept: ``heads``, ``kv_heads`` (latent
    attention: every head its own), ``window`` (none here), ``sparse``."""
    return [{
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_attention_heads"],
        "window": None,
        "sparse": i >= cfg["first_k_dense_replace"],
    } for i in range(len(cfg["layer_types"]))]


def attention_params(cfg):
    """Latent attention's matrices at the share held."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (D * rq + rq * H * (dn + dr) + D * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * D)


def connection_params(cfg):
    """One connection's coefficient matrix."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * n * (n + 2)


def dense_matmul_params(cfg):
    """Parameters that multiply every token (routed experts left out)."""
    D = cfg["hidden_size"]
    total = D * cfg["vocab_size"]                       # the untied head
    for layer in layer_shapes(cfg):
        total += attention_params(cfg) + 2 * connection_params(cfg)
        if layer["sparse"]:
            total += D * cfg["n_routed_experts_published"]   # the router
            total += (3 * D * cfg["moe_intermediate_size"]
                      * cfg["n_shared_experts"])
        else:
            total += 3 * D * cfg["intermediate_size"]
    return total


def expert_flops_per_row(cfg):
    """Forward + backward FLOPs of one routed row through one expert."""
    return 18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_attention_flops_per_step(cfg, batch, seq):
    pairs = laguna_flops.window_pairs(seq, None)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 3 * batch * sum(
        2 * (qk + cfg["v_head_dim"]) * layer["heads"] * pairs
        for layer in layer_shapes(cfg))


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv: six tensors at the keys' size (q, k twice, dq,
    dk) and six at the values' (v twice, o twice, do, dv) a head."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return itemsize * sum(
        6 * batch * seq * layer["heads"] * (qk + cfg["v_head_dim"])
        for layer in layer_shapes(cfg))


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Required FLOPs of one optimizer step; ``routed_rows`` is the
    program's count of assignments that landed on experts held here in
    the step (all expert layers together)."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + expert_flops_per_row(cfg) * routed_rows
            + train_attention_flops_per_step(cfg, batch, seq))


def grouped_matmul_bytes(cfg, routed_rows, expert_layer_calls, itemsize=2):
    """``laguna_flops.grouped_matmul_bytes`` under this family's keys."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    matrices = cfg["n_routed_experts"] * 3 * D * F
    return itemsize * (5 * routed_rows * D
                       + 3 * matrices * expert_layer_calls)


def mhc_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Forward: the streams read once for the coefficients and the pre mix
    and its output written (n + 1), the streams read again with the
    sub-layer's output and the new streams written (2 n + 1); backward:
    the new streams' gradient read and the sub-layer's output's written
    (n + 1), then the sub-layer's input's gradient, the streams, the new
    streams' gradient and the sub-layer's output read and the streams'
    gradient written (3 n + 2): 7 n + 5 [tokens, hidden] tensors for each
    of a layer's two sub-layers."""
    n = cfg["hc_mult"]
    sublayers = 2 * len(cfg["layer_types"])
    return (7 * n + 5) * sublayers * batch * seq * cfg["hidden_size"] \
        * itemsize
