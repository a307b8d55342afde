"""Helpers of the Ouro tests: the configuration cut to tiny widths (the
published shape kept: layers of four norms, two heads of 16 with rotary on
the whole head, a gated MLP, the untied head, the exit gate on its seeded
bias, the stack run four times over its own output), and a temporary copy
of the benchmark that holds it. Nothing here touches JAX at import."""

import json
import os
import shutil

import benchtiny

CELL = "ouro-2.6b.train-8k-looped-1chip"
DEPTH = 7                       # layers the committed configuration keeps
NAME = f"ouro-2.6b-{DEPTH}l"
CONFIG = f"benchmark/configs/{NAME}.json"
MIX = "train-8k-looped-1chip"

TINY = dict(
    hidden_size=32, intermediate_size=48, head_dim=16,
    num_attention_heads=2, num_key_value_heads=2, vocab_size=64,
    layer_types=["full_attention"] * 2, max_position_embeddings=64,
    smp={"microbatches": 2, "bf16": True}, module={})
TINY_MIX = dict(batch=4, seq=32, batch_pool=4,
                token_law={"kind": "zipf_mandelbrot", "offset": 8})
# Above what sound tiny runs read on the CPU, below the float8 control's.
TINY_LIMITS = {
    "loss_gap_step1": 0.004, "loss_gap_step2": 0.004,
    "loss_gap_step3": 0.004, "first_grad_norm_gap": 0.02,
    "param_change_norm_gap": 0.4,
    "pass_loss_gap_1": 0.004, "pass_loss_gap_2": 0.004,
    "pass_loss_gap_3": 0.004, "pass_loss_gap_4": 0.004,
    "exit_share_gap": 0.002, "loss_rise_over_window": 0.0,
    "flash_kernels_missing": 3,        # the CPU path has no Pallas kernels
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
    with the Ouro configuration, its mix and its limits cut to tiny."""
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
