"""The benchmark's copy of the trace generators matches the program's."""

from __future__ import annotations

import numpy as np
import pytest

from bench import traffic_gen


@pytest.mark.parametrize("trace,seed", [("azure", 42), ("azure", 2**33 + 7),
                                        ("lmsys", 5)])
def test_fleet_trace_bit_identical_to_program(trace, seed):
    from repro.traces import TraceSpec, generate_trace_columns

    want = generate_trace_columns(
        TraceSpec(trace=trace, num_requests=3000, rate=1000.0, seed=seed))
    got = traffic_gen.trace_columns(
        {"trace": trace, "rate": 1000.0, "requests": 3000}, seed)
    for name, col in got.items():
        ref = getattr(want, name)
        assert col.dtype == ref.dtype, name
        assert np.array_equal(col, ref), name


def test_open_loop_same_work_for_every_seed():
    params = {"trace": "lmsys", "rate": 6.0, "sizes_seed": 11}
    a = traffic_gen.open_loop_schedule(params, 30.0, 1, vocab=1000)
    b = traffic_gen.open_loop_schedule(params, 30.0, 2**40 + 3, vocab=1000)
    assert len(a) == len(b) == 180
    for col in ("prompt_len", "max_new", "category", "byte_len"):
        assert sorted(getattr(a, col)) == sorted(getattr(b, col))
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    def gaps(s):
        return np.sort(np.diff(np.concatenate([[0.0], s.arrival])))

    assert np.allclose(gaps(a), gaps(b))
    for s in (a, b):
        assert np.all(np.diff(s.arrival) >= 0) and s.arrival[-1] < 30.0
        assert [len(p) for p in s.prompts] == s.prompt_len.tolist()


def test_open_loop_repeats_for_one_seed():
    params = {"trace": "lmsys", "rate": 6.0, "sizes_seed": 11}
    a = traffic_gen.open_loop_schedule(params, 10.0, 9, vocab=1000)
    b = traffic_gen.open_loop_schedule(params, 10.0, 9, vocab=1000)
    assert np.array_equal(a.arrival, b.arrival) and a.prompts == b.prompts


def test_fixed_sizes_are_permuted_by_the_seed():
    """With ``sizes_seed`` every seed replays the same arrivals and the
    same multiset of requests, in an order the seed draws."""
    params = {"trace": "azure", "rate": 1000.0, "requests": 500,
              "sizes_seed": 7}
    a = traffic_gen.trace_columns(params, 2**33 + 1)
    b = traffic_gen.trace_columns(params, 2**33 + 2)
    assert np.array_equal(a["arrival_time"], b["arrival_time"])
    rows = ("byte_len", "max_output_tokens", "category", "true_input_tokens")
    key = lambda c: sorted(zip(*(c[k].tolist() for k in rows)))
    assert key(a) == key(b)
    assert not np.array_equal(a["max_output_tokens"], b["max_output_tokens"])
