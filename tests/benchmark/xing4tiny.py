"""Helpers of the Xing4.0 tests: the configuration cut to tiny widths (the
published shape kept: a leading layer with the dense MLP, then four routed
layers; latent attention with query / key heads of 24 = 16 + 8 against
value heads of 16, on 4 heads; four residual streams; 4 of 16 experts held
at 4 a token under the sigmoid law with its selection bias beside a shared
expert; the untied head), and a temporary copy of the benchmark that holds
it. Nothing here touches JAX at import."""

import json
import os
import shutil

import benchtiny

CELL = "xing4.0-29b-a4b.train-4k-group8-1chip"
CONFIG = "benchmark/configs/xing4.0-29b-a4b-5l-ep8.json"
MIX = "train-4k-group8-1chip"

TINY = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts_published=16, n_routed_experts=4,
    experts_held_first=4, num_experts_per_tok=4, vocab_size=64,
    num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=64, smp={"microbatches": 2, "bf16": True},
    module={})
TINY_MIX = dict(batch=4, seq=32, batch_pool=4,
                token_law={"kind": "zipf_mandelbrot", "offset": 8})
# Above what sound tiny runs read on the CPU, below the float8 control's.
TINY_LIMITS = {
    "loss_gap_step1": 0.04, "loss_gap_step2": 0.04,
    "loss_gap_step3": 0.04, "first_grad_norm_gap": 0.018,
    "param_change_norm_gap": 0.4, "weights_moved_in_window": 0.0,
    "flash_kernels_missing": 3,        # the CPU path has no Pallas kernels
    "moe_dropped_assignments": 0,
}


def config(**changes):
    """The committed configuration at tiny widths."""
    with open(os.path.join(benchtiny.ROOT, CONFIG)) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(changes)
    return cfg


def tiny_root(tmp_path):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` under ``tmp_path``
    with the Xing4.0 configuration, its mix and its limits cut to tiny."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(benchtiny.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(benchtiny.ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, CONFIG), "w") as f:
        json.dump(config(), f)
    mix = os.path.join(root, "benchmark", "traffic", MIX + ".json")
    with open(mix) as f:
        data = json.load(f)
    data.update(TINY_MIX)
    with open(mix, "w") as f:
        json.dump(data, f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump({"limits": TINY_LIMITS}, f)
    return root
