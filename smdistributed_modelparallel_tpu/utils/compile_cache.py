"""JAX's persistent compilation cache, placed from outside.

The entry point that compiles for the chip (``chip_smoke.py``) calls
``configure()`` once, before its first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set the directory is JAX's own business and
no directory is set in code; where it is not, the cache lives at the fixed
path ``<checkout>/.jax_cache`` (git-ignored) — never a temp dir, a pid or
the time: the path is part of the cache's key, so a directory that moves
never hits, and a second run over the same tree must find what the first one
compiled.

This is JAX's cache of XLA executables, not ``smp.exec_cache`` (the opt-in
``SMP_EXEC_CACHE`` store of whole serialized step programs).
"""

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# Every program worth keeping on this path compiles in well over a second
# (the step in ~10 s, the two serving programs in several); half a second
# keeps them and leaves out the swarm of tiny eager-op programs.
_MIN_COMPILE_SECONDS = 0.5

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def configure():
    """Turn the persistent cache on and return the directory it uses."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECONDS
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env_dir or DEFAULT_DIR


class CacheCounter:
    """Counts JAX's own cache hit/miss events from construction on.

    ``counts()`` is cumulative; callers subtract two readings to attribute
    hits and misses to the compiles in between."""

    def __init__(self):
        from jax import monitoring

        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def counts(self):
        return {"hits": self.hits, "misses": self.misses}
