"""smp.nn — tensor-parallel module library.

Parity target: reference ``torch/nn/__init__.py:24-35`` exports. Populated
across M3; the registry is available from M0.
"""

from smdistributed_modelparallel_tpu.nn.tp_registry import (
    TensorParallelismRegistry,
    tp_register,
    tp_register_with_module,
)
from smdistributed_modelparallel_tpu.nn.linear import (
    ColumnParallelLinear,
    DistributedLinear,
    RowParallelLinear,
)
from smdistributed_modelparallel_tpu.nn.embedding import DistributedEmbedding
from smdistributed_modelparallel_tpu.nn.layer_norm import (
    DistributedLayerNorm,
    FusedLayerNorm,
)
from smdistributed_modelparallel_tpu.nn.cross_entropy import (
    DistributedCrossEntropy,
    fused_lm_head_cross_entropy,
    vocab_parallel_cross_entropy,
)
from smdistributed_modelparallel_tpu.nn.softmax import (
    scaled_causal_masked_softmax,
    scaled_masked_softmax,
)
from smdistributed_modelparallel_tpu.nn.gelu import bias_gelu, gelu
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedAttentionLayer,
    DistributedTransformer,
    DistributedTransformerLayer,
    DistributedTransformerLMHead,
    DistributedTransformerOutputLayer,
)
from smdistributed_modelparallel_tpu.nn.conv import DistributedShortConv
from smdistributed_modelparallel_tpu.nn.hyper_connection import (
    DistributedHyperConnection,
)
from smdistributed_modelparallel_tpu.nn.latent_attention import (
    DistributedLatentAttentionLayer,
)
from smdistributed_modelparallel_tpu.nn.diffusion import (
    masked_diffusion_loss,
    record_diffusion_stats,
    two_copy_stream,
)
from smdistributed_modelparallel_tpu.nn.exit_gate import (
    exit_gated_loss,
    next_token_targets,
    record_exit_stats,
)
from smdistributed_modelparallel_tpu.nn.moe import (
    DistributedDroplessMoE,
    DistributedMoE,
    moe_aux_losses,
    record_moe_stats,
)
