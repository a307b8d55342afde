"""Share of device 0's busy time in latent attention's projections round
the core: ops under ``smp/latent/{q_down,q_up,kv_down,kv_up,rope}``
(the two down-projections with their latents' norms, the two
up-projections to heads, rotary on the rope parts and the heads put
together), forward, recomputed and transposed. The output projection
(``smp/latent/out``) and the kernels (``smp/attn/core``) are not in
it: every attention has those."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")

PARTS = tuple("smp/latent/" + part for part in (
    "q_down", "q_up", "kv_down", "kv_up", "rope"))


def read(ctx):
    return _moe.share_of_busy(ctx, PARTS)
