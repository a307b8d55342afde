"""Tensor-parallelism registry: maps module classes to distributed versions.

Parity target: reference ``torch/tp_registry.py:164-311``
(``TensorParallelismRegistry``): records constructor args of registered
classes, re-instantiates marked modules as their Distributed* counterparts
with translated arguments, and exposes ``smp.tp_register`` /
``smp.tp_register_with_module``. In the TPU build, modules are Flax modules;
"re-instantiation" swaps the module class at DistributedModel construction
time, with init-hook argument translation identical in spirit.
"""

from smdistributed_modelparallel_tpu.utils.exceptions import TensorParallelismError
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()


class TensorParallelismRegistry:
    def __init__(self):
        # original class -> (distributed class, init_hook, forward_hook, return_hook)
        self._map = {}
        self._translate_functions = {}  # dist class -> (to_hf, from_hf) state translators
        # ``resolver(registry, origin_cls)``, installed by the state at
        # smp.init: may register a ``transformers`` class that ``_map``
        # does not hold yet (nn/huggingface.register_predefined_hooks).
        self.late_resolver = None

    def register(self, origin_cls, dist_cls, init_hook=None, forward_hook=None,
                 return_hook=None, translate_functions=None):
        if origin_cls in self._map:
            logger.debug("Overwriting tp registration for %s", origin_cls.__name__)
        self._map[origin_cls] = (dist_cls, init_hook, forward_hook, return_hook)
        if translate_functions is not None:
            self._translate_functions[dist_cls] = translate_functions

    def _resolve_late(self, origin_cls):
        """Offer a class ``_map`` misses to the late resolver, if the class
        says it comes from ``transformers``. Any other class costs this
        one miss and one string test."""
        if (
            origin_cls not in self._map
            and self.late_resolver is not None
            and (getattr(origin_cls, "__module__", None) or "").startswith(
                "transformers."
            )
        ):
            self.late_resolver(self, origin_cls)

    def is_supported(self, origin_cls):
        self._resolve_late(origin_cls)
        return origin_cls in self._map

    def distributed_class(self, origin_cls):
        self._resolve_late(origin_cls)
        try:
            return self._map[origin_cls][0]
        except KeyError:
            raise TensorParallelismError(
                f"{origin_cls.__name__} has no registered distributed counterpart; "
                f"use smp.tp_register / smp.tp_register_with_module."
            )

    def hooks(self, origin_cls):
        self._resolve_late(origin_cls)
        _, init_hook, forward_hook, return_hook = self._map[origin_cls]
        return init_hook, forward_hook, return_hook

    def distribute(self, origin_cls, args, kwargs, tp_config=None):
        """Build the distributed counterpart of origin_cls(*args, **kwargs).

        Returns None when the init hook declines (reference T5 relative-
        bias block). When forward/return hooks are registered, the module
        is wrapped in a scope-sharing shim that applies them at call time
        (parity: reference ``DistributedModule.__call__``,
        ``torch/nn/dist_module.py:5-32``).
        """
        self._resolve_late(origin_cls)
        dist_cls, init_hook, forward_hook, return_hook = self._map[origin_cls]
        if init_hook is not None:
            hooked = init_hook(*args, **kwargs)
            if hooked is None:
                return None
            args, kwargs = hooked
        kwargs = dict(kwargs)
        if tp_config:
            kwargs.update(tp_config)
        module = dist_cls(*args, **kwargs)
        if forward_hook is not None or return_hook is not None:
            from smdistributed_modelparallel_tpu.nn.auto_distribute import (
                HookedModule,
            )

            module = HookedModule(
                inner=module, fwd_hook=forward_hook, ret_hook=return_hook
            )
        return module

    def translate_functions(self, dist_cls):
        return self._translate_functions.get(dist_cls)


def tp_register(origin_cls, init_hook=None, forward_hook=None, return_hook=None,
                translate_functions=None):
    """Decorator form: ``@smp.tp_register(nn.Linear, ...) class DistLinear``.

    Parity: reference ``torch/tp_registry.py:282-296``.
    """

    def wrap(dist_cls):
        from smdistributed_modelparallel_tpu.backend.state import state

        registry = state.tp_registry or TensorParallelismRegistry()
        state.tp_registry = registry
        registry.register(origin_cls, dist_cls, init_hook, forward_hook, return_hook,
                          translate_functions)
        return dist_cls

    return wrap


def tp_register_with_module(origin_cls, dist_cls, init_hook=None, forward_hook=None,
                            return_hook=None, translate_functions=None):
    """Function form. Parity: reference ``torch/tp_registry.py:298-310``."""
    from smdistributed_modelparallel_tpu.backend.state import state

    registry = state.tp_registry or TensorParallelismRegistry()
    state.tp_registry = registry
    registry.register(origin_cls, dist_cls, init_hook, forward_hook, return_hook,
                      translate_functions)
