"""A whole run with the timed path broken underneath reads not correct.

Each test skips only the harness's look for a chip (``require_tpu=False``)
and plants one fault in the program at the point where it produces its
answer. The faults that these cells can have: an answer altered where it
is produced (a fleet record, a served token) and a step that returns its
state unchanged (slot decode that writes no keys or values). A fleet
round whose state is unchanged never ends, so it cannot finish a run; the
cells hold no mean over a batch and no exchange between chips.
"""

from __future__ import annotations

import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def _run(root, workload, seconds=2.0):
    from bench.run import execute

    return execute(workload, 2**34 + 9, seconds, False, root=str(root),
                   require_tpu=False)


@pytest.mark.parametrize("workload", ["fleet-azure-1k.sweep16",
                                      "fleet-azure-1k.single"])
@pytest.mark.parametrize("column,delta", [("recf", 1e-3), ("reci", 1)])
def test_fleet_answer_altered(root, monkeypatch, workload, column, delta):
    from repro.sim import jax_engine

    real = jax_engine._unpack_records

    def altered(rec, n):
        rec = dict(rec)
        rec[column] = rec[column].copy()
        rec[column][..., n // 2, 1 if column == "recf" else 0] += delta
        return real(rec, n)

    monkeypatch.setattr(jax_engine, "_unpack_records", altered)
    result = _run(root, workload)
    assert result["correct"] is False


def test_fleet_answer_altered_in_last_lane_only(root, monkeypatch):
    """Every lane of the sweep is compared, so a fault in one lane shows."""
    from repro.sim import jax_engine

    real = jax_engine._unpack_records

    def altered(rec, n):
        rec = dict(rec)
        rec["reci"] = rec["reci"].copy()
        rec["reci"][-1, n // 2, 0] += 1
        return real(rec, n)

    monkeypatch.setattr(jax_engine, "_unpack_records", altered)
    result = _run(root, "fleet-azure-1k.sweep16")
    assert result["correct"] is False


def test_served_token_altered(root, monkeypatch):
    from repro.serving import engine

    real = engine.sample
    calls = {"n": 0}

    def altered(logits, rng, params):
        out = real(logits, rng, params)
        calls["n"] += 1
        if calls["n"] == 40:  # one token, mid-run, where it is sampled
            out = (out + 1) % logits.shape[-1]
        return out

    monkeypatch.setattr(engine, "sample", altered)
    result = _run(root, "yi-6b-8l.chat", 3.0)
    assert calls["n"] > 40
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"] or result["correct"] is False
    assert result["correct"] is False


def test_decode_returns_its_state_unchanged(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.serving import engine

    real = engine.build_slot_decode

    def stale(model, axes):
        fn = real(model, axes)

        def step(params, state, tokens, index):
            logits, _ = fn(params, jax.tree.map(jnp.copy, state), tokens,
                           index)
            return logits, state

        return step

    monkeypatch.setattr(engine, "build_slot_decode", stale)
    result = _run(root, "yi-6b-8l.chat", 3.0)
    assert result["correct"] is False
