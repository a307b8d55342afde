"""``chip_smoke.py`` off the chip: it must refuse to report, and its phases
must hold their own checks at a tiny size on the CPU mesh.

The real run needs the TPU (``python chip_smoke.py``, ``--chips 4``); here
the phases are called as functions with the kernel-presence checks off (the
CPU has no Mosaic kernels to find) and everything else on: finite falling
losses against the plain reference, fused-CE agreement, engine tokens against
``smp.generate``, parameter shards on four devices, pp and tp collectives.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax

from smdistributed_modelparallel_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def counter():
    return compile_cache.CacheCounter()


@pytest.fixture
def tiny(smoke):
    return smoke.OneChipSize(
        overrides=(("d_model", 64), ("n_layers", 2), ("n_heads", 4),
                   ("vocab_size", 512)),
        batch=4, seq=128, microbatches=2, steps=4, lr=1e-3,
        prompt_lens=(5, 16, 16, 40), new_tokens=8,
    )


def _phases(capsys):
    return {
        rec["phase"]: rec
        for rec in map(json.loads, capsys.readouterr().out.splitlines())
    }


def test_no_tpu_exits_nonzero_without_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "found no TPU" in out.stderr


@pytest.mark.parametrize(
    "var", ["SMP_DISABLE_PALLAS_ATTN", "SMP_DISABLE_FUSED_CE"]
)
def test_kernel_switches_are_refused(smoke, monkeypatch, var):
    monkeypatch.setenv(var, "1")
    with pytest.raises(SystemExit) as e:
        smoke.describe_environment(1)
    assert var in str(e.value)


def test_one_chip_phases_pass_their_checks(smoke, tiny, counter, capsys):
    smoke.one_chip(tiny, counter, expect_kernels=False)
    phases = _phases(capsys)
    assert set(phases) == {"train", "fused_ce", "serve"}
    train = phases["train"]
    assert train["losses"][-1] < train["losses"][0]
    assert train["step0_abs_diff"] <= smoke.LOSS_TOLERANCE
    # Off the chip no kernel is in the program, and the line says so.
    assert train["attention_path"] == "xla_jnp" and train["kernels"] == {}
    assert phases["fused_ce"]["abs_diff"] <= smoke.LOSS_TOLERANCE
    serve = phases["serve"]
    assert serve["programs"] == ["decode", "prefill"]
    assert serve["exact_matches"] + len(serve["divergences"]) == 4


def test_kernel_check_fails_the_phase_off_chip(smoke, tiny, counter, capsys):
    """With the kernel checks ON, as on the chip, a program without the
    flash kernels fails the train phase."""
    with pytest.raises(smoke.SmokeFailure, match="flash attention kernels"):
        smoke.train_phase(tiny, counter, expect_kernels=True)


def test_failed_phase_means_no_ok_line(smoke, counter, capsys, monkeypatch):
    fake = type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(
        smoke, "describe_environment", lambda chips: ([fake], counter)
    )

    def broken(size, counter):
        raise smoke.SmokeFailure("loss did not fall")

    monkeypatch.setattr(smoke, "one_chip", broken)
    with pytest.raises(smoke.SmokeFailure):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_phase_passes_its_checks(smoke, counter, capsys):
    if jax.device_count() < 4:
        pytest.skip("needs four virtual devices")
    size = smoke.FourChipSize(
        layers=4, heads=4, d_model=64, vocab=512, batch=4, seq=64,
        microbatches=2, steps=3, lr=1e-3,
    )
    smoke.sharded_phase(size, counter, expect_kernels=False)
    rec = _phases(capsys)["sharded_train"]
    assert rec["mesh"]["pp"] == 2 and rec["mesh"]["tp"] == 2
    assert len(rec["mesh_device_ids"]) == 4
    assert rec["losses"][-1] < rec["losses"][0]
    assert rec["collectives_by_axis"]["pp"]
    assert rec["collectives_by_axis"]["tp"]
    shares = rec["param_shard_bytes_by_device"].values()
    assert all(0 < b < rec["param_bytes_total"] for b in shares)


class TestCompileCacheHelper:
    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        from jax.experimental.compilation_cache import compilation_cache

        names = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
        before = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure() == str(tmp_path)
        # No directory is set in code when the variable is.
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_path_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.configure() == want
        assert compile_cache.configure() == want  # same path every time
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs <= 1.0
