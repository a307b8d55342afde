"""What ``smp.init`` imports, and the tp_registry's late look-up.

``smp.init`` used to register the predefined Hugging Face hooks for every
architecture, which imports ``transformers``, ``torch`` and ``tensorflow``:
half a minute of every start of a program that holds no Hugging Face
class. The registry now resolves a ``transformers`` class's hook when it
first meets the class. Nothing here needs ``transformers`` installed: the
positive path runs on a stand-in module under that package's name
(``tests/test_huggingface.py`` runs it on the real classes).
"""

import json
import os
import subprocess
import sys
import types

import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.nn.huggingface import (
    register_predefined_hooks,
)
from smdistributed_modelparallel_tpu.nn.tp_registry import (
    TensorParallelismRegistry,
)
from smdistributed_modelparallel_tpu.utils.exceptions import (
    TensorParallelismError,
)
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HEAVY = ("transformers", "torch", "tensorflow")

_FRESH_PROCESS = """
import json, sys
import flax.linen as nn
import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.nn import DistributedLinear
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

HEAVY = ("transformers", "torch", "tensorflow")
facts = {}

def note(when):
    facts[when] = {
        "loaded": [m for m in HEAVY if m in sys.modules],
        "resolved": telemetry.counter("smp_hf_hooks_resolved").value,
    }

class Net(nn.Module):
    first: nn.Module
    second: nn.Module

    def __call__(self, x):
        return self.second(nn.relu(self.first(x)))

smp.init({})
note("after_init")
with smp.tensor_parallelism():
    first = nn.Dense(16)
model = smp.DistributedModel(Net(first=first, second=nn.Dense(4)))
facts["swapped"] = isinstance(model.module.first, DistributedLinear)
note("after_distributed_model")
print("FACTS " + json.dumps(facts))
"""


@pytest.fixture(scope="module")
def fresh_process():
    """smp.init, then a Flax tree through DistributedModel, in an
    interpreter of their own: this one may hold ``transformers`` already."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [_REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run(
        [sys.executable, "-c", _FRESH_PROCESS], capture_output=True,
        text=True, timeout=300, env=env, cwd=_REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("FACTS ")][-1]
    return json.loads(line[len("FACTS "):])


@pytest.mark.parametrize("module", _HEAVY)
def test_smp_init_does_not_import(fresh_process, module):
    assert module not in fresh_process["after_init"]["loaded"]


def test_no_hook_is_resolved_by_smp_init(fresh_process):
    assert fresh_process["after_init"]["resolved"] == 0


def test_a_flax_tree_through_distributed_model_imports_none_of_them(
        fresh_process):
    # The registry was consulted: the marked Dense was swapped.
    assert fresh_process["swapped"]
    assert fresh_process["after_distributed_model"] == {
        "loaded": [], "resolved": 0}


class _Dist:
    def __init__(self, **kw):
        self.kw = kw


def _asked(registry):
    """Install a resolver that records what it is asked and registers
    nothing."""
    asked = []
    registry.late_resolver = lambda reg, cls: asked.append(cls)
    return asked


class TestLateResolver:
    def test_a_registry_with_no_resolver_misses_plainly(self):
        registry = TensorParallelismRegistry()
        cls = type("X", (), {"__module__": "transformers.models.x"})
        assert not registry.is_supported(cls)
        with pytest.raises(TensorParallelismError):
            registry.distributed_class(cls)

    @pytest.mark.parametrize("module", [
        "flax.linen.linear", "smdistributed_modelparallel_tpu.nn.linear",
        "__main__", "transformers_mine.modeling", "transformers", None,
    ])
    def test_only_a_class_from_transformers_is_offered(self, module):
        registry = TensorParallelismRegistry()
        asked = _asked(registry)
        cls = type("GPT2LMHeadModel", (), {"__module__": module})
        assert not registry.is_supported(cls)
        assert asked == []

    def test_every_reader_of_the_map_offers_a_miss_once_registered_never(self):
        registry = TensorParallelismRegistry()
        asked = _asked(registry)
        cls = type("X", (), {"__module__": "transformers.models.x.modeling_x"})
        assert not registry.is_supported(cls)
        with pytest.raises(TensorParallelismError):
            registry.distributed_class(cls)
        for reader in (registry.hooks,
                       lambda c: registry.distribute(c, (), {})):
            with pytest.raises(KeyError):
                reader(cls)
        assert asked == [cls] * 4
        registry.register(cls, _Dist)
        assert registry.is_supported(cls)
        assert registry.distributed_class(cls) is _Dist
        assert registry.hooks(cls) == (None, None, None)
        assert registry.distribute(cls, (), {"a": 1}).kw == {"a": 1}
        assert asked == [cls] * 4


@pytest.fixture
def stand_in(monkeypatch):
    """A module under ``transformers``' name that defines a class called
    as one of GPT-2's architectures is; ``sys.modules`` is as it was
    afterwards."""
    name = "transformers.models.gpt2.stand_in_for_tests"
    module = types.ModuleType(name)
    module.GPT2LMHeadModel = type("GPT2LMHeadModel", (), {"__module__": name})
    module.Unknown = type("Unknown", (), {"__module__": name})
    monkeypatch.setitem(sys.modules, name, module)
    return module


@pytest.fixture
def registry(fresh_tp_registry):
    return fresh_tp_registry


def _resolved():
    return telemetry.counter("smp_hf_hooks_resolved").value


class TestPredefinedHooksAsTheResolver:
    def test_smp_init_installs_it_and_registers_no_class_of_transformers(
            self, registry):
        assert registry.late_resolver is register_predefined_hooks
        assert not [c for c in registry._map
                    if c.__module__.startswith("transformers.")]
        assert _resolved() == 0

    def test_an_architecture_resolves_once_to_its_family(self, registry, stand_in):
        from smdistributed_modelparallel_tpu.nn.huggingface import gpt2
        from smdistributed_modelparallel_tpu.nn.transformer import (
            DistributedTransformerLMHead,
        )

        cls = stand_in.GPT2LMHeadModel
        assert registry.is_supported(cls)
        assert registry.distributed_class(cls) is DistributedTransformerLMHead
        assert _resolved() == 1
        config = types.SimpleNamespace(
            n_embd=32, n_head=2, n_layer=2, vocab_size=64, n_positions=32,
            n_inner=None, attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0,
            layer_norm_epsilon=1e-5, activation_function="gelu_new",
            scale_attn_weights=True, scale_attn_by_inverse_layer_idx=False,
            reorder_and_upcast_attn=False, initializer_range=0.02,
        )
        init_hook, _, _ = registry.hooks(cls)
        assert init_hook(config, deterministic=True) == (
            (), {**gpt2.config_to_smp(config), "deterministic": True})
        assert _resolved() == 1

    def test_a_class_of_transformers_with_no_hook_stays_a_miss(
            self, registry, stand_in):
        assert not registry.is_supported(stand_in.Unknown)
        assert _resolved() == 0

    def test_a_class_its_module_does_not_define_is_not_taken(
            self, registry, stand_in):
        claims = type("GPT2LMHeadModel", (), {"__module__": stand_in.__name__})
        nowhere = type("GPT2LMHeadModel", (),
                       {"__module__": "transformers.models.gpt2.not_loaded"})
        assert not registry.is_supported(claims)
        assert not registry.is_supported(nowhere)
        assert _resolved() == 0

    def test_an_explicit_registration_wins(self, registry, stand_in):
        cls = stand_in.GPT2LMHeadModel
        smp.tp_register_with_module(cls, _Dist)
        assert registry.distributed_class(cls) is _Dist
        assert registry.distribute(cls, (), {"a": 1}).kw == {"a": 1}
        assert _resolved() == 0
