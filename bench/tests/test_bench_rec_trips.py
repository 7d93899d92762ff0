"""``fleet.rec_trips_per_round`` divides the program's record-write trips
by its rounds, and gives nothing for a program without the counter."""

from __future__ import annotations

import pytest

from bench import core


@pytest.mark.parametrize("stats, want", [
    ({"rounds": 8, "rec_trips": 6}, 0.75),
    ({"rounds": 8, "adm_waves": 5}, None),  # a program without the counter
    ({"rounds": 0, "rec_trips": 0}, None),
    ({}, None),  # no run yet
])
def test_reader(monkeypatch, stats, want):
    from repro.sim import jax_engine

    reader = core.load_module(
        core.ROOT / "bench" / "layers" / "fleet.rec_trips_per_round.py",
        "fleet.rec_trips_per_round")
    monkeypatch.setattr(jax_engine, "_LAST_RUN", dict(stats))
    assert reader.read(None) == want
