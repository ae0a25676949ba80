"""The `server_eigh_pct` reader on serve records with and without the
program's `meta.server_eigh` count."""
import types

import pytest

import benchpath  # noqa: F401
import run


def _read(*metas):
    view = types.SimpleNamespace(records=[{"meta": m} for m in metas])
    return run._module("metrics", "server_eigh_pct").read(view)


@pytest.mark.parametrize("counts,value", [
    ([{"rounds": 0, "of": 8}], 0.0),
    ([{"rounds": 8, "of": 8}], 100.0),
    ([{"rounds": 1, "of": 4}, {"rounds": 2, "of": 4}], 37.5)])
def test_share_of_rounds_served(counts, value):
    assert _read(*[{"server_eigh": c} for c in counts]) == pytest.approx(value)


def test_a_program_without_the_count_reads_nothing():
    assert _read({"spans": {}}) is None
    assert _read() is None
