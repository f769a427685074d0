"""The reduction from a profiler trace to busy time, idle share, the
operations that took most time and what the host did in the gaps, on a
small recorded trace; and the loader on a trace taken here."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        raw = json.load(f)
    return {"device": {int(chip): {line: tr.line_of(events)
                                   for line, events in lines.items()}
                       for chip, lines in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_window_is_the_harness_annotation(trace):
    assert tr.window_of(trace, "bench:window") == (0, 1_000_000)
    with pytest.raises(ValueError):
        tr.window_of(trace, "bench:nothing")


def test_busy_is_the_union_clipped_to_the_window(trace):
    ops = trace["device"][0]["XLA Ops"]
    busy = tr.busy_intervals(ops, 0, 1_000_000).tolist()
    # fusion.1 and fusion.2 overlap 25000..30000: counted once; the op
    # that starts at 990000 is cut at the window's end
    assert busy == [[10000, 50000], [52000, 70000], [70100, 90000],
                    [200000, 230000], [600000, 640000], [990000, 1000000]]
    assert sum(e - s for s, e in busy) == 157_900
    assert tr.busy_intervals(ops, 40000, 60000).tolist() == [
        [40000, 50000], [52000, 60000]]
    assert tr.busy_intervals(tr.line_of([]), 0, 10).tolist() == []


def test_gaps_are_what_busy_leaves(trace):
    busy = tr.busy_intervals(trace["device"][0]["XLA Ops"], 0, 1_000_000)
    gaps = tr.idle_gaps(busy, 0, 1_000_000).tolist()
    assert gaps == [[0, 10000], [50000, 52000], [70000, 70100],
                    [90000, 200000], [230000, 600000], [640000, 990000]]
    assert sum(e - s for s, e in gaps) == 1_000_000 - 157_900


def test_gaps_go_to_the_innermost_host_event(trace):
    busy = tr.busy_intervals(trace["device"][0]["XLA Ops"], 0, 1_000_000)
    by = tr.attribute_gaps(tr.idle_gaps(busy, 0, 1_000_000), trace["host"])
    assert by == {
        # three gaps shorter than MIN_NAMED_GAP_NS: 10000 + 2000 + 100
        "(between operations)": 12100,
        # 90000..200000: its middle, 145000, lies in "plan" in query a
        "plan": 110000,
        # 230000..600000: middle 415000, in copy_to_host within fetch
        "ArrayImpl.copy_to_host": 370000,
        # 640000..990000: middle 815000, in query b's fetch
        "fetch": 350000}
    assert sum(by.values()) == 1_000_000 - 157_900
    import numpy as np

    assert tr.attribute_gaps(np.array([[0, 50000]]), []) == {
        "(no host event)": 50000}


def test_operations_are_named_by_module_without_its_id(trace):
    ops = tr.op_seconds(trace["device"][0], 0, 1_000_000)
    assert ops == {"jit_agg_fn/fusion.1": 58000, "jit_agg_fn/fusion.2": 64900,
                   "jit_merge/sort.3": 30000, "fusion.9": 10000}


def test_reduce_trace(trace):
    out = tr.reduce_trace(trace, 0, 1_000_000, chips=1, top=2)
    assert out["busy_s"] == pytest.approx(157_900e-9)
    assert out["window_s"] == pytest.approx(1_000_000e-9)
    idle_pct = 100 * (1 - out["busy_s"] / out["window_s"])
    assert idle_pct == pytest.approx(84.21)
    assert out["device_ops"] == [["jit_agg_fn/fusion.2", 64900e-9],
                                 ["jit_agg_fn/fusion.1", 58000e-9]]
    assert [g[0] for g in out["idle_gaps"]] == ["ArrayImpl.copy_to_host",
                                                "fetch"]
    with pytest.raises(ValueError):
        tr.reduce_trace(trace, 0, 1_000_000, chips=4)
    with pytest.raises(ValueError):
        tr.reduce_trace({"device": {}, "host": []}, 0, 1)


def test_lines_other_than_ops_count_where_a_plane_has_no_ops_line():
    lines = {"XLA Modules": tr.line_of([("m(1)", 0, 10)]),
             "Other": tr.line_of([("x", 2, 4)])}
    assert tr.busy_intervals(tr.ops_of(lines), 0, 10).tolist() == [[2, 4]]


def test_an_op_is_named_by_what_stands_before_its_equals_sign():
    hlo = "%while.4 = (u32[]{:T(128)}, f32[3,4]{1,0:T(4,128)}) while(...)"
    assert tr.short_name(hlo) == "while.4"
    assert tr.short_name("fusion.7") == "fusion.7"
    assert len(tr.short_name("x" * 500)) == 80


def test_load_trace_reads_a_profile_taken_here(tmp_path):
    """The loader on a real .xplane.pb: off the chip the CPU client's
    threads stand for the device, in rehearsals only."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(1000)).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench:query:x"):
                    f(jnp.ones(1000)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert tr.load_trace(path)["device"] == {}  # no TPU plane here
    loaded = tr.load_trace(path, cpu_stand_in=True)
    t0, t1 = tr.window_of(loaded, "bench:window")
    out = tr.reduce_trace(loaded, t0, t1)
    assert 0 < out["busy_s"] < out["window_s"]
    assert sum(n == "bench:query:x" for n, _, _ in loaded["host"]) == 3
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / "nothing"))
