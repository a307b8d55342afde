"""M5 checkpoint/resume tests.

Mirrors the reference checkpoint tier (``test/torch/mpi_hybrid/
test_checkpoint_api.py`` / ``test_tp_checkpoint.py``): save/load round
trips, newest-pointer resume, retention GC, config verification, deferred
application.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.nn.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedTransformerLMHead,
)
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPRuntimeError,
    SMPValidationError,
)

TINY = dict(
    num_layers=2, num_attention_heads=2, attention_head_size=8,
    hidden_size=16, intermediate_size=32, vocab_size=64, num_positions=32,
    causal_mask_size=32, pre_layernorm=True, post_layernorm=False,
    final_layernorm=True, attention_dropout_prob=0.0,
    hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
)


def _setup(cfg=None, **head):
    smp.shutdown()
    smp.init(cfg or {"microbatches": 2})
    m = DistributedTransformerLMHead(**dict(TINY, **head))
    model = smp.DistributedModel(m)
    opt = smp.DistributedOptimizer(optax.adamw(1e-3), model)

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        loss = jnp.mean(vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:]))
        model.backward(loss)
        return loss

    ids = jax.random.randint(jax.random.key(0), (4, 16), 0, 64)
    return model, opt, train_step, ids


class TestSaveLoad:
    def test_partial_roundtrip(self, tmp_path):
        model, opt, step_fn, ids = _setup()
        step_fn(model, ids)
        f = str(tmp_path / "obj.pt")
        written = smp.save({"a": np.arange(4)}, f)
        assert written.endswith("_0_0_0.pt")
        back = smp.load(f)
        np.testing.assert_array_equal(back["a"], np.arange(4))

    def test_v2_format_autodetect(self, tmp_path):
        _setup()
        import pickle

        with open(str(tmp_path / "obj_0_0.pt"), "wb") as fh:
            pickle.dump({"x": 1}, fh)
        assert smp.load(str(tmp_path / "obj.pt"))["x"] == 1

    def test_missing_raises(self, tmp_path):
        _setup()
        with pytest.raises(SMPRuntimeError):
            smp.load(str(tmp_path / "nope.pt"))


class TestSaveCheckpointDir:
    @pytest.mark.parametrize(
        "cfg,head",
        [
            (None, {}),
            # The untied head's kernel is one more sharded leaf: split on
            # its vocabulary over tp x pp (PR 30), it comes back whole.
            ({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 2},
             {"tie_input_output_embedding": False}),
        ],
        ids=["one_device", "pp2_tp2_untied_head"],
    )
    def test_roundtrip_with_newest(self, tmp_path, cfg, head):
        model, opt, step_fn, ids = _setup(cfg, **head)
        step_fn(model, ids)
        opt.step()
        loss_before = float(step_fn(model, ids).reduce_mean())
        saved = jax.device_get(model.state_dict())
        smp.save_checkpoint(str(tmp_path), tag="t1", user_content={"epoch": 3})

        assert (tmp_path / "newest").read_text() == "t1"
        assert (tmp_path / "t1_partial" / "model_shards_p0.npz").exists()
        assert (tmp_path / "t1_partial" / "optimizer_shards_p0.npz").exists()

        # Perturb, resume, verify restoration.
        model.params = jax.tree_util.tree_map(lambda p: p * 0.0, model.params)
        user = smp.resume_from_checkpoint(str(tmp_path))
        assert user == {"epoch": 3}
        loss_after = float(step_fn(model, ids).reduce_mean())
        np.testing.assert_allclose(loss_before, loss_after, atol=1e-5)
        restored = jax.device_get(model.state_dict())
        assert set(restored) == set(saved)
        for name, leaf in saved.items():
            np.testing.assert_array_equal(restored[name], leaf, err_msg=name)
        if head:
            assert model.params["lm_head"]["kernel"].sharding.spec[1] \
                == ("tp", "pp")
            assert saved["lm_head/kernel"].shape == (16, 64)

    def test_retention_gc(self, tmp_path):
        model, opt, step_fn, ids = _setup()
        step_fn(model, ids)
        for i in range(4):
            smp.save_checkpoint(
                str(tmp_path), tag=f"t{i}", num_kept_partial_checkpoints=2
            )
        kept = sorted(d for d in os.listdir(tmp_path) if d.endswith("_partial"))
        assert kept == ["t2_partial", "t3_partial"]

    def test_config_mismatch_rejected(self, tmp_path):
        model, opt, step_fn, ids = _setup()
        step_fn(model, ids)
        smp.save_checkpoint(str(tmp_path), tag="t1")
        # Re-init with different parallelism; with elastic resume disabled
        # the reference's fatal verify_smp_config behavior is preserved.
        # (The elastic-by-default reshard path is covered in
        # tests/test_resilience.py::TestElasticResume.)
        smp.shutdown()
        smp.init({"microbatches": 2, "tensor_parallel_degree": 2, "ddp": True})
        with pytest.raises(SMPValidationError):
            smp.resume_from_checkpoint(str(tmp_path), elastic=False)

    def test_deferred_application(self, tmp_path):
        model, opt, step_fn, ids = _setup()
        step_fn(model, ids)
        opt.step()
        ref_leaf = np.asarray(
            jax.tree_util.tree_leaves(model.params)[0]
        ).copy()
        smp.save_checkpoint(str(tmp_path), tag="t1")

        # Fresh session: resume BEFORE the model exists.
        smp.shutdown()
        smp.init({"microbatches": 2})
        smp.resume_from_checkpoint(str(tmp_path), load_optimizer=False)
        assert state.loaded_model_state is not None
        m = DistributedTransformerLMHead(**TINY)
        model2 = smp.DistributedModel(m)

        @smp.step
        def fwd(model, ids):
            logits = model(ids)
            loss = jnp.mean(
                vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
            )
            model.backward(loss)
            return loss

        fwd(model2, ids)
        got = np.asarray(jax.tree_util.tree_leaves(model2.params)[0])
        np.testing.assert_allclose(got, ref_leaf, atol=1e-6)

    def test_full_checkpoint(self, tmp_path):
        model, opt, step_fn, ids = _setup()
        step_fn(model, ids)
        smp.save_checkpoint(str(tmp_path), tag="full1", partial=False)
        assert (tmp_path / "full1").exists()
        model.params = jax.tree_util.tree_map(lambda p: p * 0.0, model.params)
        smp.resume_from_checkpoint(str(tmp_path), partial=False)
        total = sum(
            float(np.sum(np.abs(np.asarray(l))))
            for l in jax.tree_util.tree_leaves(model.params)
        )
        assert total > 0.0


@pytest.mark.slow
class TestShardedCheckpoint:
    """True per-rank sharded checkpoints (VERDICT r2 item 6): each global
    element is stored exactly once across the shard files, and loading
    materializes only shard-sized pieces — never the full tree."""

    def _setup(self, cfg):
        smp.reset()
        smp.init(cfg)
        from smdistributed_modelparallel_tpu.nn.transformer import (
            DistributedTransformerLMHead,
        )
        from smdistributed_modelparallel_tpu.nn.cross_entropy import (
            vocab_parallel_cross_entropy,
        )

        module = DistributedTransformerLMHead(
            num_layers=4, num_attention_heads=4, attention_head_size=8,
            hidden_size=32, intermediate_size=64, vocab_size=96,
            num_positions=32, causal_mask_size=32,
            pre_layernorm=True, post_layernorm=False, final_layernorm=True,
            attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0,
        )
        model = smp.DistributedModel(module)
        opt = smp.DistributedOptimizer(optax.adam(1e-3), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids)
            loss = jnp.mean(
                vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
            )
            model.backward(loss)
            return loss

        ids = jax.random.randint(jax.random.key(0), (8, 16), 0, 96)
        return model, opt, train_step, ids

    def test_pp_tp_rdp_roundtrip_no_full_tree(self, tmp_path):
        cfg = {"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
               "microbatches": 2, "ddp": True}
        model, opt, step_fn, ids = self._setup(cfg)
        step_fn(model, ids)
        opt.step()
        step_fn(model, ids)
        opt.step()
        want = jax.device_get(model.state_dict())
        want_opt = {
            k: np.asarray(v)
            for k, v in jax.device_get(opt.state_dict()).items()
        }
        smp.save_checkpoint(str(tmp_path), tag="s1", model=model,
                            optimizer=opt)

        # Storage efficiency: every global element exactly once (a full
        # gather per process would store mesh-size copies).
        f = np.load(tmp_path / "s1_partial" / "model_shards_p0.npz")
        stored = sum(int(np.prod(f[k].shape)) * f[k].dtype.itemsize
                     for k in f.files)
        unique = sum(l.nbytes for l in jax.tree_util.tree_leaves(model.params))
        assert stored == unique, (stored, unique)

        # Fresh world: resume BEFORE params exist (deferred apply), then
        # spy that reassembly happens shard-wise for tp-sharded leaves.
        model2, opt2, step_fn2, _ = self._setup(cfg)
        from smdistributed_modelparallel_tpu import shard_io

        regions = []
        orig = shard_io.ShardCatalog.assemble

        def spy(self, key, index, shape, dtype):
            regions.append((key, tuple(
                (0 if s.start is None else s.start,
                 d if s.stop is None else s.stop)
                for s, d in zip(index, shape)), tuple(shape)))
            return orig(self, key, index, shape, dtype)

        shard_io.ShardCatalog.assemble = spy
        try:
            smp.resume_from_checkpoint(str(tmp_path), tag="s1")
            step_fn2(model2, ids)  # init triggers deferred sharded load
        finally:
            shard_io.ShardCatalog.assemble = orig

        got = jax.device_get(model2.state_dict())
        for k in want:
            np.testing.assert_allclose(got[k], want[k], atol=1e-6, err_msg=k)
        # tp-sharded leaves were assembled in shard-sized pieces, not whole.
        partial_reads = [
            r for r in regions
            if any((b - a) < d for (a, b), d in zip(r[1], r[2]))
        ]
        assert partial_reads, "no shard-wise reads observed"

        # Optimizer state restored too (deferred path).
        opt2._ensure_state()
        got_opt = {
            k: np.asarray(v)
            for k, v in jax.device_get(opt2.state_dict()).items()
        }
        for k in want_opt:
            np.testing.assert_allclose(
                got_opt[k], want_opt[k], atol=1e-6, err_msg=k
            )

        # Training continues.
        out = step_fn2(model2, ids)
        opt2.step()
        assert np.isfinite(float(out.reduce_mean()))


class TestAsyncSave:
    """Non-blocking saves (TPU extension): background writes of captured
    immutable trees, submission-order `newest`, drained errors."""

    def _tiny_model(self):
        smp.reset()
        smp.init({"microbatches": 1})
        module = DistributedTransformerLMHead(
            num_layers=1, num_attention_heads=2, attention_head_size=4,
            hidden_size=8, intermediate_size=16, vocab_size=32,
            num_positions=8, causal_mask_size=8, attention_dropout_prob=0.0,
            hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
        )
        model = smp.DistributedModel(module)
        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids)
            loss = jnp.mean(
                vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
            )
            model.backward(loss)
            return loss

        ids = jax.random.randint(jax.random.key(0), (2, 8), 0, 32)
        return model, opt, train_step, ids

    def test_async_snapshot_is_exact(self, tmp_path):
        """The save captures the tree at submission time, even though the
        optimizer keeps swapping the model to new trees while it drains."""
        model, opt, step_fn, ids = self._tiny_model()
        step_fn(model, ids)
        opt.step()
        want = np.asarray(
            jax.device_get(model.params["word_embedding"]["embedding"])
        )
        smp.save_checkpoint(str(tmp_path), tag="a1", model=model,
                            optimizer=opt, blocking=False)
        for _ in range(3):  # keep training while the save drains
            step_fn(model, ids)
            opt.step()
        smp.wait_for_checkpoints()

        model2, opt2, step_fn2, _ = self._tiny_model()
        smp.resume_from_checkpoint(str(tmp_path), tag="a1")
        step_fn2(model2, ids)  # triggers deferred apply
        got = np.asarray(
            jax.device_get(model2.params["word_embedding"]["embedding"])
        )
        np.testing.assert_allclose(got, want, atol=1e-6)
        # ...and training moved on: current params differ from the snapshot.
        now = np.asarray(
            jax.device_get(model.params["word_embedding"]["embedding"])
        )
        assert not np.allclose(now, want)

    def test_submission_order_newest(self, tmp_path):
        model, opt, step_fn, ids = self._tiny_model()
        step_fn(model, ids)
        opt.step()
        smp.save_checkpoint(str(tmp_path), tag="t1", model=model, blocking=False)
        smp.save_checkpoint(str(tmp_path), tag="t2", model=model, blocking=False)
        smp.wait_for_checkpoints()
        with open(tmp_path / "newest") as fh:
            assert fh.read() == "t2"

    def test_errors_surface_on_wait(self, tmp_path):
        model, opt, step_fn, ids = self._tiny_model()
        step_fn(model, ids)
        smp.save_checkpoint(str(tmp_path), tag="ok", model=model, blocking=False)
        smp.wait_for_checkpoints()  # clean save drains fine
        # Sabotage: the job's target directory path exists as a FILE, so
        # the background write fails and the error surfaces on wait.
        (tmp_path / "bad_partial").write_text("")
        smp.save_checkpoint(str(tmp_path), tag="bad", model=model,
                            blocking=False)
        with pytest.raises(Exception):
            smp.wait_for_checkpoints()
        # The queue is drained after the failure is reported.
        smp.wait_for_checkpoints()
