"""Operations and bytes a step or a kernel *requires*, from shapes alone.

What is counted, and why every share computed from it stays under 100%:

- Matmuls: 2 FLOPs per multiply-add, forward; the backward pass costs twice
  the forward (one product for the input's gradient, one for the weight's),
  so training is 6 FLOPs per matmul parameter per token. The output head
  counts (tied or not, it is a [d, V] product per token); the embedding
  lookups do not (they are gathers).
- Attention: the two products QK^T and PV over the *causal* half of the
  score matrix: a query at position t (0-based) needs t+1 keys, so a
  sequence of T positions needs T(T+1)/2 query-key pairs, not T^2. A kernel
  that skips masked blocks does no more than this; counting full scores
  would let it read over 100% of the peak.
- Recomputed work (activation checkpointing, the flash backward's second
  pass over the scores) is not counted: it is not required by the
  mathematics. So the count is a lower bound of what the device executes,
  and count / (time x peak) cannot pass 100% unless time leaves work out.
- Element-wise work (LayerNorm, GELU, softmax, the optimizer) is not
  counted: it does not run on the matrix unit the peak is quoted for.
"""


def matmul_params(cfg):
    """Parameters that take part in a matmul for each token, head included.
    ``cfg`` holds Hugging Face names of either family (GPT-2: ``n_embd``,
    ``n_layer``, ``n_inner``; GPT-NeoX: ``hidden_size``,
    ``num_hidden_layers``, ``intermediate_size``)."""
    d = cfg.get("n_embd", cfg.get("hidden_size"))
    layers = cfg.get("n_layer", cfg.get("num_hidden_layers"))
    d_ff = cfg.get("n_inner") or cfg.get("intermediate_size") or 4 * d
    per_layer = 3 * d * d + d * d + 2 * d * d_ff     # qkv, proj, fc, proj
    return layers * per_layer + d * cfg["vocab_size"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def attention_forward_flops(cfg, seq):
    """QK^T and PV for one sequence of ``seq`` positions, all layers,
    causal: 2 products x 2 FLOPs x d_model per query-key pair."""
    d = cfg.get("n_embd", cfg.get("hidden_size"))
    layers = cfg.get("n_layer", cfg.get("num_hidden_layers"))
    return layers * 4 * d * causal_pairs(seq)


def train_flops_per_step(cfg, batch, seq):
    """Required FLOPs of one optimizer step over ``batch`` sequences of
    ``seq`` tokens: forward + backward = 3 x forward."""
    tokens = batch * seq
    matmul = 6 * matmul_params(cfg) * tokens
    attention = 3 * attention_forward_flops(cfg, seq) * batch
    return matmul + attention


def train_attention_flops_per_step(cfg, batch, seq):
    """The attention kernels' share of ``train_flops_per_step``: forward
    (2 products) + backward (4 products: dV, dP, dQ, dK) over the causal
    pairs. The backward kernels' recomputation of the scores is not
    counted."""
    return 3 * attention_forward_flops(cfg, seq) * batch


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Least HBM traffic of the attention kernels in one step: forward
    reads q, k, v and writes o (4 tensors); backward reads q, k, v, o, do
    and writes dq, dk, dv (8 tensors); each [batch, seq, d_model]."""
    d = cfg.get("n_embd", cfg.get("hidden_size"))
    layers = cfg.get("n_layer", cfg.get("num_hidden_layers"))
    return layers * 12 * batch * seq * d * itemsize
