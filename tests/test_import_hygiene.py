"""Import + configuration-surface hygiene.

1. Importing the package must not initialize any accelerator backend: a
   module-level device-array (e.g. ``jnp.float32(...)`` as a constant)
   would eagerly initialize the platform at import — and a process that
   merely imports the package would then take the chip, which belongs to
   one process at a time (including the multiprocessing spawn children of
   the native-bus tests, which don't run conftest's cpu pin).

2. Every ``SMP_*`` environment variable referenced anywhere in the source
   tree must appear in README.md's environment-variable table, so new
   knobs cannot ship undocumented.
"""

import os
import re
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_does_not_initialize_backend():
    code = (
        "import smdistributed_modelparallel_tpu\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'package import initialized a JAX backend'\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "clean" in out.stdout


def _iter_source_files():
    roots = [
        os.path.join(_REPO, "smdistributed_modelparallel_tpu"),
        os.path.join(_REPO, "scripts"),
    ]
    files = [
        os.path.join(_REPO, "bench.py"),
        os.path.join(_REPO, "__graft_entry__.py"),
        os.path.join(_REPO, "tests", "conftest.py"),
    ]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files.extend(
                os.path.join(dirpath, n) for n in names if n.endswith(".py")
            )
    return [f for f in files if os.path.exists(f)]


def test_every_smp_env_var_is_documented():
    """Any SMP_* knob referenced in source must be in README's env table."""
    pattern = re.compile(r"\bSMP_[A-Z0-9_]+\b")
    referenced = {}
    for path in _iter_source_files():
        with open(path, encoding="utf-8") as f:
            for var in pattern.findall(f.read()):
                referenced.setdefault(var, os.path.relpath(path, _REPO))
    assert referenced, "env-var scan found nothing — scan roots broken?"
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    undocumented = sorted(
        f"{var} (referenced in {where})"
        for var, where in referenced.items()
        if f"`{var}`" not in readme
    )
    assert not undocumented, (
        "SMP_* env vars referenced in source but missing from README.md's "
        "environment-variable table:\n  " + "\n  ".join(undocumented)
    )
