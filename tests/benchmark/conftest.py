"""Every assertion about ``BENCHMARK.json`` holds on the committed manifest
and on a copy grown as a later PR grows it (``benchtiny.grow``: entries
appended last, files added): a test that takes ``manifest`` runs on both."""

import pytest

import benchtiny


@pytest.fixture(scope="session")
def roots(tmp_path_factory):
    return {"committed": benchtiny.ROOT,
            "grown": benchtiny.grown_root(tmp_path_factory.mktemp("grown"))}


@pytest.fixture(params=["committed", "grown"])
def manifest(request, roots):
    from benchmark import loader

    return loader.Manifest(roots[request.param])
