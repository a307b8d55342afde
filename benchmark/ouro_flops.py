"""Operations and bytes a looped training step *requires*, from shapes
alone. The counting is ``flops.py``'s (2 FLOPs a multiply-add, backward
twice the forward, attention over the causal half, recomputation and
element-wise work, the sixteen norms a token a pass among it, not counted);
what differs is that every layer's products, its attention, the head and
the exit gate's product run ``total_ut_steps`` times a forward on one set
of parameters, so a parameter is counted once for each pass.

This module stands where ``drivers/train_steps.py`` names ``flops``
(``drivers/train_steps_looped.py`` puts it there).
"""

from benchmark.flops import causal_pairs


def passes(cfg):
    return int(cfg.get("total_ut_steps", 1))


def layers(cfg):
    return len(cfg["layer_types"])


def layer_matmul_params(cfg):
    """One layer's q, k, v, o and gate, up, down."""
    D = cfg["hidden_size"]
    E = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * D * E + 3 * D * cfg["intermediate_size"]


def pass_matmul_params(cfg):
    """Parameters that multiply a token in one pass: the layers, the head
    and the exit gate (the table is a gather)."""
    D = cfg["hidden_size"]
    return (layers(cfg) * layer_matmul_params(cfg)
            + D * cfg["vocab_size"] + D)


def attention_forward_flops(cfg, seq):
    """QK^T and PV of one sequence in every layer of every pass, causal."""
    E = cfg["num_attention_heads"] * cfg["head_dim"]
    return passes(cfg) * layers(cfg) * 4 * E * causal_pairs(seq)


def forward_flops_per_token(cfg, seq):
    return (2 * passes(cfg) * pass_matmul_params(cfg)
            + attention_forward_flops(cfg, seq) / seq)


def train_attention_flops_per_step(cfg, batch, seq):
    return 3 * attention_forward_flops(cfg, seq) * batch


def train_flops_per_step(cfg, batch, seq):
    """Required FLOPs of one optimizer step over ``batch`` sequences of
    ``seq`` tokens: forward + backward = 3 x forward."""
    return (6 * passes(cfg) * pass_matmul_params(cfg) * batch * seq
            + train_attention_flops_per_step(cfg, batch, seq))


def train_attention_bytes_per_step(cfg, batch, seq, itemsize=2):
    """Least HBM traffic of the attention kernels in one step: forward 4
    tensors, backward 8, each [batch, seq, heads x head size], a layer a
    pass."""
    E = cfg["num_attention_heads"] * cfg["head_dim"]
    return passes(cfg) * layers(cfg) * 12 * batch * seq * E * itemsize


def layer_passes_per_step(cfg, microbatches):
    return passes(cfg) * layers(cfg) * microbatches
