"""Import + configuration-surface hygiene.

1. Importing the package must not initialize any accelerator backend: a
   module-level device-array (e.g. ``jnp.float32(...)`` as a constant)
   would eagerly initialize the platform at import — and a process that
   merely imports the package would then take the chip, which belongs to
   one process at a time (including the multiprocessing spawn children of
   the native-bus tests, which don't run conftest's cpu pin).

2. Every ``SMP_*`` environment variable referenced anywhere in the source
   tree must appear in README.md's environment-variable table, so new
   knobs cannot ship undocumented; and every row of that table must name
   a variable the source still reads, so a deleted knob takes its row
   with it.

3. The documents a reader is sent to name only tools that exist.
"""

import os
import re
import subprocess
import sys

import pytest

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
        os.path.join(_REPO, "chip_smoke.py"),
        os.path.join(_REPO, "__graft_entry__.py"),
        os.path.join(_REPO, "tests", "conftest.py"),
    ]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files.extend(
                os.path.join(dirpath, n) for n in names if n.endswith(".py")
            )
    return [f for f in files if os.path.exists(f)]


_SMP_VAR = re.compile(r"\bSMP_[A-Z0-9_]+\b")


def _read(relpath):
    with open(os.path.join(_REPO, relpath), encoding="utf-8") as f:
        return f.read()


def _referenced_smp_vars():
    referenced = {}
    for path in _iter_source_files():
        where = os.path.relpath(path, _REPO)
        for var in _SMP_VAR.findall(_read(where)):
            referenced.setdefault(var, where)
    assert referenced, "env-var scan found nothing — scan roots broken?"
    return referenced


def test_every_smp_env_var_is_documented():
    """Any SMP_* knob referenced in source must be in README's env table."""
    referenced = _referenced_smp_vars()
    readme = _read("README.md")
    undocumented = sorted(
        f"{var} (referenced in {where})"
        for var, where in referenced.items()
        if f"`{var}`" not in readme
    )
    assert not undocumented, (
        "SMP_* env vars referenced in source but missing from README.md's "
        "environment-variable table:\n  " + "\n  ".join(undocumented)
    )


def test_no_orphaned_env_table_rows():
    """The mirror: a row of README's environment table whose variable no
    scanned source file names documents a knob that is gone."""
    referenced = _referenced_smp_vars()
    table = _read("README.md").split("### Environment variables", 1)[1]
    table = table.split("\n## ", 1)[0]
    rows = [line.split("|")[1] for line in table.splitlines()
            if line.startswith("| `SMP_")]
    assert len(rows) > 50, "environment table not found — heading moved?"
    orphaned = sorted(
        var for cell in rows for var in _SMP_VAR.findall(cell)
        if var not in referenced
    )
    assert not orphaned, (
        "README.md's environment table documents SMP_* variables that no "
        "source file reads:\n  " + "\n  ".join(orphaned)
    )


# ``train.py`` stands for the reader's own program in every example.
_READERS_OWN = {"train.py"}


@pytest.mark.parametrize("doc", [
    "README.md", "MIGRATION.md", "PERF.md", "benchmark/README.md",
    ".claude/skills/verify/SKILL.md",
])
def test_live_documents_name_only_tools_that_exist(doc):
    """Every ``scripts/<name>.py`` a live document names, and every
    ``python <path>.py`` command it shows, is a file of this checkout."""
    text = _read(doc)
    named = set(re.findall(r"\bscripts/\w+\.py\b", text))
    named.update(
        path for path in re.findall(r"\bpython3? +([\w./-]+\.py)\b", text)
        if path not in _READERS_OWN
    )
    missing = sorted(
        path for path in named
        if not os.path.exists(os.path.join(_REPO, path))
    )
    assert not missing, f"{doc} names files that do not exist: {missing}"

