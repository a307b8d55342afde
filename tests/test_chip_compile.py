"""The Pallas kernels of the main path, compiled by the chip's own compiler.

Interpret mode accepts kernels the TPU compiler refuses (too much scoped
VMEM, an unaligned slice). The compiler is installed here and compiles for a
chip that is described, not attached — nothing runs, nothing is timed. The
shapes are the three widths ``BASELINE.json`` names (GPT-2-124M, GPT-2-1.5B,
GPT-J-6B).

Only one process at a time may load the TPU library, so the topology is
described inside a fixture (never at import) and everything compiles in the
test's own process; keep these tests in this one file.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2. While the module runs the compile cache is
    off (a described-chip entry cannot be read back and the next compile
    would warn) and the matmul precision is JAX's default, as on the chip:
    ``conftest.py`` pins "highest" for the CPU parity tests, under which
    Mosaic refuses the bf16 dots or asks for more VMEM."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """HLO text of ``fn`` compiled for the described chip. ``shapes`` are
    bf16 shape tuples, or ``(shape, dtype)`` pairs."""
    args = []
    for s in shapes:
        shape, dtype = s if isinstance(s[0], tuple) else (s, jnp.bfloat16)
        args.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return jax.jit(fn).lower(*args).compile().as_text()


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


@pytest.mark.parametrize(
    "shape", [(2, 1024, 12, 64), (1, 2048, 16, 256), (2, 1024, 25, 64)],
    ids=["gpt2_124m", "gptj_6b", "gpt2_xl_microbatch"],
)
def test_flash_attention_forward_backward(one_chip, shape):
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, causal=True))

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip, shape, shape, shape
    )
    # forward, dq and dk/dv passes
    assert text.count("tpu_custom_call") >= 3
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert name in text


@pytest.mark.parametrize("positions,dtype,asks", [
    (8192, jnp.bfloat16, False), (8192, jnp.float32, True),
    (7680, jnp.float32, True), (7168, jnp.float32, False)],
    ids=["bf16_step", "f32_init_pass", "f32_15MiB", "f32_14MiB"])
def test_flash_attention_at_8k_with_every_head_its_own_kv_head(
        one_chip, positions, dtype, asks):
    """16 heads of 128 on 16 KV heads (the looped cell's attention). In
    bfloat16 at 8,192 positions, the compiled step, the call is what it
    was (no scoped limit asked for); in float32, the eager init pass of a
    model whose step runs in bfloat16, a head's K and V in two buffers
    fill the default scoped limit and the compiler refused the call by
    0.3 MiB, and at 7,680 positions (15 MiB) by 0.4: the call asks for
    what it holds. At 7,168 (14 MiB) it compiles unasked."""
    from smdistributed_modelparallel_tpu.ops import pallas_attention as pa

    shape = ((1, positions, 16, 128), dtype)
    params = pa._whole_operand_params(
        positions, 128, 128, jnp.dtype(dtype).itemsize, 256, 256 * 512, None)
    assert bool(params) is asks
    text = _compile(
        jax.grad(lambda q, k, v: _sum32(pa.flash_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2)),
        one_chip, shape, shape, shape)
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert name in text


def test_flash_backward_by_global_ids_with_bias_and_dropout(one_chip):
    """The cp ring's backward block pair (ids mode, fp32 out) with a
    key-padding bias, dropout and a window of the global heads. The dkv
    pass's tile is keys-major, so what belongs to a program's own keys (its
    global ids, its slice of the bias) is turned from a row of lanes into a
    [block_k, 1] column: a relayout interpret mode takes whatever its
    shape, this compiler only where it can make it."""
    from smdistributed_modelparallel_tpu.ops import pallas_attention as pa

    T = 2048

    def backward(q, k, v, o, g, lse, q_ids, kv_ids, kpad, seed, head0):
        return pa.flash_bwd_with_ids(
            q, k, v, o, g, lse, kpad, q_ids, kv_ids, scale=0.088,
            causal=True, seed=seed, dropout_rate=0.1, counter_len=4 * T,
            head0=head0, head_total=16)

    heads = (1, T, 4, 128)
    text = _compile(
        backward, one_chip, heads, heads, heads, heads, heads,
        ((1, 4, T), jnp.float32), ((T,), jnp.int32), ((T,), jnp.int32),
        ((1, T), jnp.float32), ((), jnp.int32), ((), jnp.int32))
    for name in ("smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert name in text


def test_which_flash_calls_ask_for_scoped_memory():
    """A call under the block-diffusion mask asks whatever its size; the
    float32 forward of 4 heads of 192 on values of 128 at 4,096 positions
    (an accepted cell's init pass: 12 MiB of operands) does not."""
    from smdistributed_modelparallel_tpu.ops import pallas_attention as pa

    assert pa._whole_operand_params(256, 128, 128, 2, 256, 256 * 256, 4)
    assert not pa._whole_operand_params(
        4096, 256, 128, 4, 256, 256 * 512, None)


def test_flash_attention_with_value_heads_of_their_own_size(one_chip):
    """Latent attention's heads at their published sizes (4 heads a chip,
    T 4096): query and key heads of 192 run at 256 lanes, value heads of
    128 at their own 128, in all three kernels."""
    import re

    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, causal=True, scale=0.14468))

    qk, v = (1, 4096, 4, 192), (1, 4096, 4, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qk, qk, v)
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert name in text
    calls = [line for line in text.split("\n")
             if "tpu_custom_call" in line and "smp_flash_" in line]
    # the forward's output and the dkv pass's dv are 128 wide, dq and dk 256
    shapes = " ".join(re.findall(r"bf16\[4,4096,(\d+)\]", " ".join(
        line.split(" custom-call(")[0] for line in calls)))
    assert sorted(shapes.split()) == ["128", "128", "256", "256"]


def test_flash_attention_under_the_stage_vmap_on_a_mesh(topo, monkeypatch):
    """Pythia-1.4B's attention (16 heads of 128, T 2048, a microbatch of 1)
    as the pipeline executors run it at pp 2 x tp 2: ``stage_vmap`` over
    two stages, the kernels in ``_flash_on_mesh``'s manual region. The
    vmap names pp, so each chip's three Mosaic calls take its own stage's 8
    heads, ``[8, 2048, 128]``, and nothing crosses pp; unnamed, they took
    ``[2, 8, 2048, 128]`` behind three all-gathers and an all-reduce over
    pp (PR 28). Interpret mode has accepted what this compiler refused
    (PR 21), hence here as well as on the CPU mesh."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops import attention
    from smdistributed_modelparallel_tpu.parallel.pipeline import stage_vmap
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    monkeypatch.delenv("SMP_DISABLE_PALLAS_ATTN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 2}, devices=topo.devices[:4])

    def loss(q, k, v):
        return _sum32(attention.attention_core(q, k, v, causal=True))

    staged = NamedSharding(state.mesh, P("pp", None, None, "tp", None))
    text = _compile(
        stage_vmap(jax.grad(loss, argnums=(0, 1, 2)), 2), staged,
        *[(2, 1, 2048, 16, 128)] * 3,
    )
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert any(name in line for line in calls)
    blocks = {dims for line in calls
              for dims in re.findall(r"bf16\[([\d,]+)\]", line)}
    assert blocks == {"8,2048,128"}
    census = hlo_audit.collective_census(text, state.mesh)
    assert not [(op, axis) for op, entry in census.items()
                for axis in entry["axes"] if "pp" in axis]


def test_untied_lm_head_vjp_keeps_its_vocabulary_split_on_a_mesh(topo):
    """Pythia-1.4B's head and loss (T 2048, d 2048, V 50,304 = 4 x 12,576,
    bf16 in, the cell's fp32 loss) as a backward tick differentiates them at
    pp 2 x tp 2. ``lm_head`` is born split on the vocabulary over tp and pp
    and its logits are held to that, so each chip's three products are
    12,576 columns wide and the log-sum-exp and the target logit are
    all-reduced over the four chips; unsplit, every chip ran all 50,304
    (PR 30). The failure sign is a gathered ``f32[1,2047,50304]``."""
    import re

    import flax.linen as nn
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec as P

    import smdistributed_modelparallel_tpu as smp
    from benchmark.builders import neox_tp
    from benchmark.loader import Manifest
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    cfg = dict(Manifest().cell("pythia-1.4b.train-pp2tp2").config,
               num_hidden_layers=2)
    seq, d, vocab = 2048, cfg["hidden_size"], cfg["vocab_size"]
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 8}, devices=topo.devices[:4])
    module = neox_tp.module(cfg)
    key = jax.random.key(0)            # eager: outside the described mesh
    with jax.set_mesh(state.mesh):
        born = jax.eval_shape(
            module.init, key, jax.ShapeDtypeStruct((1, seq), jnp.int32),
        )["params"]
    born = {name: born[name] for name in ("ln_f", "lm_head")}
    specs = nn.get_partition_spec(born)
    assert specs["lm_head"]["kernel"] == P(None, ("tp", "pp"))

    def on_mesh(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(state.mesh, spec))

    head_params = jax.tree_util.tree_map(
        lambda leaf, spec: on_mesh(leaf.shape, jnp.bfloat16, spec),
        meta.unbox(born), specs,
    )

    def loss(params, hidden, ids):
        logits = module.apply({"params": params}, hidden, method="head")
        logits = logits[:, :-1].astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        head_params, on_mesh((1, seq, d), jnp.bfloat16, P()),
        on_mesh((1, seq), jnp.int32, P()),
    ).compile().as_text()

    products = [line for line in text.splitlines()
                if re.search(r" (convolution|dot)\(", line)]
    assert len(products) == 3           # logits, d hidden, d kernel
    assert all("lm_head/dot_general" in line for line in products)
    dims = {int(n) for shape in re.findall(r"\b\w+\[([\d,]+)\]", text)
            for n in shape.split(",")}
    assert vocab // 4 in dims
    assert not {vocab, vocab // 2} & dims
    census = hlo_audit.collective_census(text, state.mesh)
    assert set(census) == {"all-reduce"}


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_flash_attention_eight_query_heads_on_one_kv_head(one_chip, window):
    """Mellum's share of a layer at the cell's shapes: 8 query heads of 128
    on one KV head over 8,192 positions (the dkv pass keeps 8 fp32
    partials), at the published window of 1,024 and with none."""
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, causal=True, window=window))

    q, kv = (1, 8192, 8, 128), (1, 8192, 1, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert name in text


def test_flash_attention_under_the_block_diffusion_mask(one_chip):
    """SDAR's attention as one chip of the eight-chip group runs it: 4
    query heads on 1 KV head of 128 over a two-copy stream of 16,384
    positions, blocks of 4. Each program keeps a head's whole K and V (in
    the dkv pass Q and dO) in VMEM, 16 MiB with the pipeline's second
    buffers, so the three calls ask for their scoped limit; the mask is
    made from indices on a row and a column (the compiler refuses a select
    between boolean tiles, which interpret mode accepts)."""
    import re

    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, block_diffusion=4))

    q, kv = (1, 16384, 4, 128), (1, 16384, 1, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        call, = [c for c in calls if name in c.split(" = ")[0]]
        limit, = re.findall(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)
        assert int(limit) == 2 * 2 * 16384 * 128 * 2 + (16 << 20)
        # no [2L, 2L] array: the call's operands are q, k, v and rows
        assert "16384,16384" not in call
    assert "16384,16384" not in text


# (q, kv, mask, whole and masked tiles a head and pass): SDAR's two-copy
# stream, a stream whose middle no tile of rows or keys ends at (every
# leading and trailing edge loop present: four loops in the forward and the
# dq pass, six in the dkv pass), Mellum's window layer and its full one, a
# GPT-2-XL microbatch.
_TILE_CASES = {
    "sdar": ((1, 16384, 4, 128), (1, 16384, 1, 128),
             dict(block_diffusion=4), (480, 96)),
    "stream_not_in_tiles": ((1, 8400, 4, 128), (1, 8400, 1, 128),
                            dict(block_diffusion=4), (84, 101)),
    "mellum_window": ((1, 8192, 8, 128), (1, 8192, 1, 128),
                      dict(causal=True, window=1024), (0, 90)),
    "mellum_full": ((1, 8192, 8, 128), (1, 8192, 1, 128),
                    dict(causal=True), (0, 272)),
    "gpt2_xl": ((4, 1024, 25, 64), (4, 1024, 25, 64),
                dict(causal=True), (0, 6)),
}


def _tile_gauges():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    metrics = telemetry.report()["metrics"]
    return {
        p: tuple(
            next(s["value"] for s in metrics[f"smp_flash_tiles_{kind}"][
                "series"] if s["labels"]["pass"] == p)
            for kind in ("whole", "masked"))
        for p in ("fwd", "dq", "dkv")}


def test_flash_kernels_lower_with_every_edge_tile_loop(one_chip):
    """Under the block-diffusion mask each pass walks its whole tiles with
    no mask in the body and its edge tiles with one: three or four loops a
    kernel at SDAR's shape (the test above), and where no tile ends at the
    stream's middle, as here, four in the forward and the dq pass and six
    in the dkv pass, for the parent's two. The chip's compiler takes all
    three kernels (no body past VMEM, no static range it cannot take), and
    the gauges a traced call sets read the mask's own counts."""
    name = "stream_not_in_tiles"
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    q, kv, mask, tiles = _TILE_CASES[name]

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, **mask))

    telemetry.reset()
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    for kernel in ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert kernel in text
    assert _tile_gauges() == {"fwd": tiles, "dq": tiles, "dkv": tiles}


@pytest.mark.parametrize(
    "name", ["sdar", "mellum_window", "mellum_full", "gpt2_xl"])
def test_flash_tile_gauges_at_the_cells_shapes(name):
    """``smp_flash_tiles_whole{pass}`` / ``smp_flash_tiles_masked{pass}``
    of a traced call (nothing compiles) at the default 256 x 512 tiles:
    480 / 96 a head over SDAR's 2 x 8,192 positions in blocks of 4; under
    the causal masks every visited tile masked (272 at 8,192, 90 under a
    window of 1,024, 6 at 1,024)."""
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    q, kv, mask, tiles = _TILE_CASES[name]

    def loss(q, k, v):
        return _sum32(flash_attention(q, k, v, **mask))

    telemetry.reset()
    jax.eval_shape(
        jax.grad(loss, argnums=(0, 1, 2)),
        *[jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (q, kv, kv)])
    assert _tile_gauges() == {"fwd": tiles, "dq": tiles, "dkv": tiles}


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "full_remat"])
def test_a_checkpointed_layer_compiles_one_flash_forward(one_chip, kept):
    """The chip's compiler on a checkpointed layer scan at Mellum's
    attention shapes (8 query heads of 128 on one KV head over 8,192
    positions, d 2304): under ``remat_policy`` the compiled step holds the
    forward kernel once, under the ``forward`` phase, where full remat
    holds it a second time under ``recompute``; ``hlo_audit.kernel_census``
    reads that off the text, as ``smp_kernel_calls`` publishes it."""
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from smdistributed_modelparallel_tpu.parallel.memory import remat_policy
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    T, H, hd, D = 8192, 8, 128, 2304

    def layer(x, w):
        w_qkv, w_out = w
        q, k, v = jnp.split(x @ w_qkv, [H * hd, (H + 1) * hd], axis=-1)
        o = flash_attention(q.reshape(1, T, H, hd), k.reshape(1, T, 1, hd),
                            v.reshape(1, T, 1, hd), causal=True)
        return x + o.reshape(1, T, H * hd) @ w_out

    body = jax.checkpoint(layer, policy=remat_policy() if kept else None)

    def loss(w_qkv, w_out, x):
        y, _ = jax.lax.scan(lambda c, w: (body(c, w), None), x,
                            (w_qkv, w_out))
        return _sum32(y)

    text = _compile(
        jax.grad(loss, argnums=(0, 1)), one_chip,
        (2, D, (H + 2) * hd), (2, H * hd, D), (1, T, D))
    census = hlo_audit.kernel_census(hlo_audit.op_records(text))
    assert census["smp_flash_fwd"] == {
        "forward": 1, "recompute": 0 if kept else 1, "backward": 0}
    for name in ("smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        assert census[name] == {"forward": 0, "recompute": 0, "backward": 1}


@pytest.mark.parametrize(
    "d_model,vocab", [(768, 50257), (1600, 50257), (4096, 50400)],
    ids=["gpt2_124m", "gpt2_1p5b", "gptj_6b"],
)
def test_fused_ce_forward_backward(one_chip, d_model, vocab):
    """``auto_blocks`` sizes the blocks from a VMEM budget that was held
    against this compiler: the dw backward kernel asks ~1.45x what the
    budget formula counts."""
    from smdistributed_modelparallel_tpu.ops import pallas_ce

    rows = 2048
    bn, bv = pallas_ce.auto_blocks(d_model)

    def loss(x, w, t):
        return jnp.sum(
            pallas_ce.fused_lm_head_ce(x, w, t, bn, bv, False, 0.0)
        )

    text = _compile(
        jax.grad(loss, argnums=(0, 1)), one_chip,
        (rows, d_model), (vocab, d_model), ((rows,), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 3
    for name in ("smp_ce_fwd", "smp_ce_bwd_dx", "smp_ce_bwd_dw"):
        assert name in text


@pytest.mark.parametrize(
    "d_in,d_out", [(768, 2304), (4096, 12288)], ids=["gpt2_124m", "gptj_6b"]
)
def test_matmul_bias(one_chip, d_in, d_out):
    from smdistributed_modelparallel_tpu.ops.pallas_qkv import matmul_bias

    text = _compile(
        lambda x, w, b: matmul_bias(x, w, b), one_chip,
        (2048, d_in), (d_in, d_out), (d_out,),
    )
    assert "tpu_custom_call" in text and "smp_matmul_bias" in text


@pytest.mark.parametrize(
    "features", [3072, 6400, 16384],
    ids=["gpt2_124m", "gpt2_1p5b", "gptj_6b"],
)
def test_bias_gelu_forward_backward(one_chip, features):
    """Whole-row blocks asked 25-48 MiB of scoped VMEM at the two wide
    sizes (limit 16 MiB); the kernel tiles the feature dim now."""
    from smdistributed_modelparallel_tpu.ops import pallas_gelu

    assert pallas_gelu._fits(features)

    def loss(x, b):
        return _sum32(pallas_gelu.bias_gelu(x, b))

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
        (2048, features), (features,),
    )
    assert text.count("tpu_custom_call") >= 2
    assert "smp_bias_gelu_fwd" in text and "smp_bias_gelu_bwd" in text


def test_bias_gelu_refuses_what_cannot_fit():
    """An untileable width (no 128-multiple divisor) keeps whole rows; past
    the VMEM budget ``bias_gelu_ok`` says no instead of the compiler."""
    from smdistributed_modelparallel_tpu.ops import pallas_gelu

    assert pallas_gelu._feature_block(6400) == 640
    assert pallas_gelu._feature_block(16384) == 1024
    assert pallas_gelu._feature_block(100) == 100
    wide_odd = 128 * 1024 + 8
    assert pallas_gelu._feature_block(wide_odd) == wide_odd
    assert not pallas_gelu._fits(wide_odd)
    pallas_gelu.FORCE_INTERPRET, was = True, pallas_gelu.FORCE_INTERPRET
    try:
        assert pallas_gelu.bias_gelu_ok("gelu", features=6400)
        assert not pallas_gelu.bias_gelu_ok("gelu", features=wide_odd)
    finally:
        pallas_gelu.FORCE_INTERPRET = was


def _compile_held_experts(one_chip, differentiate, tokens, D, F, held, rows):
    """HLO text of ``differentiate`` (``jax.grad``, ``jax.value_and_grad``)
    of the sum of ``held_experts_output`` at a cell's held shapes, eight
    assignments a token, compiled for the described chip."""
    from smdistributed_modelparallel_tpu.nn import moe

    def loss(x, w_gate_up, w_down, weights, tok, offsets):
        return jnp.sum(moe.held_experts_output(
            x, w_gate_up, w_down, weights, tok, offsets, "silu", rows))

    assignments = tokens * 8
    return _compile(
        differentiate(loss, argnums=(0, 1, 2, 3)), one_chip,
        (tokens, D), (held, D, 2 * F), (held, F, D),
        ((assignments,), jnp.float32), ((assignments,), jnp.int32),
        ((held + 1,), jnp.int32))


@pytest.mark.parametrize(
    "tokens,D,F,held,rows",
    [(8192, 2304, 896, 16, 6144), (8192, 3072, 1024, 8, 1024)],
    ids=["mellum_held_16", "laguna_held_8"],
)
def test_held_experts_backward_sums_weight_gradients_in_the_kernel(
        one_chip, monkeypatch, tokens, D, F, held, rows):
    """The backward of ``held_experts_output`` at both expert cells' held
    shapes: the two ``smp_grouped_wgrad`` calls are in the chunk loop with
    the fp32 sums as aliased operands, and no grouped product or fusion
    there makes a weight-shaped array (the ``ragged-dot`` transposes and
    the ``convert_add_fusion``s are gone). What is left of shape
    ``bf16[held, D, 2F]`` are the weights themselves, their layout copies
    and the final cast of the sum. The dispatch asks
    ``jax.default_backend()``, which is the CPU here: the test says TPU,
    after compiling the CPU's answer (the products) to see the check live."""
    import smdistributed_modelparallel_tpu as smp

    # The kernel stands aside on a mesh of several devices, and the mesh
    # test above leaves one behind.
    smp.shutdown()

    def compiled():
        return _compile_held_experts(
            one_chip, jax.grad, tokens, D, F, held, rows)

    def weight_products(text):
        """Lines whose grouped product or convert-and-add fusion makes an
        array of a weight tensor's shape."""
        shapes = (f"[{held},{D},{2 * F}]", f"[{held},{F},{D}]")
        return [
            line for line in text.splitlines()
            if ("ragged-dot" in line or "convert_add_fusion" in line)
            and line.split(" = ", 1)[-1].split("{", 1)[0].endswith(shapes)]

    assert len(weight_products(compiled())) == 4     # the check is live
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = compiled()
    assert weight_products(text) == []
    calls = [line for line in text.splitlines()
             if "smp_grouped_wgrad" in line and "custom-call(" in line]
    assert len(calls) == 2, calls
    assert any(f"= f32[{held},{D},{2 * F}]" in c for c in calls)
    assert any(f"= f32[{held},{F},{D}]" in c for c in calls)
    assert all("output_to_operand_aliasing" in c
               and "smp/moe/experts/smp_grouped_wgrad" in c for c in calls)


@pytest.mark.parametrize(
    "tokens,D,F,held,rows,engages",
    [(8192, 2304, 896, 16, 6144, True), (16384, 2048, 768, 16, 6144, True),
     (8192, 3072, 1024, 8, 1024, False)],
    ids=["mellum_held_16", "sdar_held_16", "laguna_held_8"],
)
def test_held_experts_sum_their_rows_back_in_the_kernel(
        one_chip, monkeypatch, tokens, D, F, held, rows, engages):
    """``held_experts_output`` forward and backward at the three expert
    cells' held shapes. Mellum's and SDAR's chunks (6,144 rows for 8,192
    and 16,384 tokens): one ``smp_row_scatter_add`` call in each chunk
    loop under ``smp/moe/combine``, the fp32 sum its aliased operand, and
    no scatter left under that scope. Laguna's 1,024-row chunks would
    stream 197 KB of the sum a row: the kernel stands aside and XLA's two
    scatter-adds stay. As above the test says TPU after compiling the
    CPU's answer to see the check live."""
    import smdistributed_modelparallel_tpu as smp

    smp.shutdown()

    def compiled():
        return _compile_held_experts(
            one_chip, jax.value_and_grad, tokens, D, F, held, rows)

    def scatters(text):
        return [line for line in text.splitlines()
                if " scatter(" in line and "smp/moe/combine" in line]

    assert len(scatters(compiled())) == 2            # the check is live
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = compiled()
    calls = [line for line in text.splitlines()
             if "smp_row_scatter_add" in line and "custom-call(" in line]
    if not engages:
        assert calls == [] and len(scatters(text)) == 2
        return
    assert scatters(text) == []
    assert len(calls) == 2, calls
    assert all(f"= f32[{tokens},{D}]" in c
               and "output_to_operand_aliasing={{}: (" in c
               and "while/body" in c
               and "smp/moe/combine/smp_row_scatter_add" in c for c in calls)
    assert sum("transpose(" in c for c in calls) == 1    # one a pass


def _computations(text):
    """``{header: body text}`` of a compiled module's computations, the
    header up to its parameters (``ENTRY %main.42``, ``%region_0.40``)."""
    found, header = {}, None
    for line in text.splitlines():
        if line.endswith("{") and " -> " in line and not line.startswith(" "):
            header = line.split(" (", 1)[0]
            found[header] = []
        elif header is not None:
            found[header].append(line)
    return {header: "\n".join(body) for header, body in found.items()}


@pytest.mark.parametrize(
    "tokens,D,F,held,rows",
    [(8192, 2304, 896, 16, 6144), (16384, 2048, 768, 16, 6144),
     (8192, 3072, 1024, 8, 1024), (8192, 2048, 1536, 8, 3072)],
    ids=["mellum_held_16", "sdar_held_16", "laguna_held_8", "lfm2_held_8"],
)
def test_held_experts_backward_chunk_is_three_products_on_weights_laid_once(
        one_chip, monkeypatch, tokens, D, F, held, rows):
    """The backward of ``held_experts_output`` at the four expert cells'
    held shapes, the chain written out (``_chunk_grads``): the chunk
    loop's body holds three ``ragged-dot`` kernels (the first product run
    again, ``g @ w_down^T`` in fp32, ``d_h @ w_gate_up^T``; the second
    product is not formed) and no ``copy`` of a weight-shaped array: both
    tensors are re-laid for their transposed use in the entry, once a
    call. The test says TPU as the tests above do, so the weight
    gradients are the kernel's and no fourth and fifth product."""
    import smdistributed_modelparallel_tpu as smp

    smp.shutdown()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    computations = _computations(_compile_held_experts(
        one_chip, jax.grad, tokens, D, F, held, rows))
    weight_shaped = tuple(
        f"[{held},{a},{b}]"
        for a, b in ((D, 2 * F), (2 * F, D), (F, D), (D, F)))

    def products(body):
        return re.findall(r"= (\w+\[[\d,]*\])\S* custom-call\(.*"
                          r"ragged_dot_tiling", body)

    def weight_copies(body):
        return [line for line in body.splitlines()
                if re.search(r" = \w+\[[\d,]*\]\S* copy\(", line)
                and line.split(" = ", 1)[1].split("{", 1)[0].endswith(
                    weight_shaped)]

    loop, = (body for body in computations.values() if products(body))
    assert sorted(products(loop)) == sorted([
        f"bf16[{rows},{2 * F}]", f"f32[{rows},{F}]", f"bf16[{rows},{D}]"])
    assert weight_copies(loop) == []
    copied_in = [header for header, body in computations.items()
                 if weight_copies(body)]
    assert len(copied_in) == 1 and copied_in[0].startswith("ENTRY")
