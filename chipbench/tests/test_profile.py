"""The reduction from a profiler trace to busy, idle and per-program time."""
import json
from pathlib import Path

import pytest

from chipbench.profile import load_events, module_seconds, reduce_events

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _op(name, start, dur, line="XLA Ops"):
    return (DEV, line, name, float(start), float(dur))


def test_busy_gaps_and_programs_by_hand():
    events = [
        (HOST, "python3", "chipbench_window", 0.0, 1000.0),
        (HOST, "python3", "chipbench_search", 0.0, 600.0),
        (HOST, "python3", "chipbench_search", 700.0, 300.0),
        _op("jit_elastic_chunk(1)", 100, 200, "XLA Modules"),
        _op("%fusion.1 = f32[8] fusion(x)", 100, 150),
        _op("%fusion.2 = f32[8] fusion(y)", 200, 100),  # overlaps fusion.1
        _op("%while = (s32[]) while(t), body=b", 100, 200),  # holds the others
        _op("jit_scatter(2)", 800, 50, "XLA Modules"),
        _op("%scatter.1 = f32[8] scatter(z)", 800, 50),
        _op("%late = f32[8] fusion(w)", 1900, 10),  # after the window
    ]
    s = reduce_events(events)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(250e-9)  # [100, 300) and [800, 850)
    assert s["module_s"] == pytest.approx({"jit_elastic_chunk": 200e-9, "jit_scatter": 50e-9})
    assert module_seconds(s, "elastic_chunk") == pytest.approx(200e-9)
    names = dict(s["device_ops"])
    assert "jit_elastic_chunk/while" not in names  # a loop's time is its body's
    assert names["jit_elastic_chunk/fusion.1"] == pytest.approx(150e-9)
    assert names["jit_scatter/scatter.1"] == pytest.approx(50e-9)
    gaps = s["idle_gaps"]
    # [300, 800) straddles the searches' gap: its middle (550) is in search 1;
    # [0, 100) in search 1; [850, 1000) in search 2
    assert gaps[0] == ("in_search", pytest.approx(500e-9))
    assert sorted(g[1] for g in gaps) == pytest.approx([100e-9, 150e-9, 500e-9])


def test_between_searches_gap_is_named_so():
    events = [
        (HOST, "python3", "chipbench_window", 0.0, 100.0),
        (HOST, "python3", "chipbench_search", 0.0, 40.0),
        (HOST, "python3", "chipbench_search", 60.0, 40.0),
        _op("%a = f32[] add(x)", 0, 45),
        _op("%b = f32[] add(y)", 55, 45),
    ]
    assert reduce_events(events)["idle_gaps"] == [("between_searches", pytest.approx(10e-9))]


def test_nothing_to_read_gives_none():
    assert reduce_events([(HOST, "python3", "other", 0.0, 1.0)]) is None
    assert reduce_events([(HOST, "python3", "chipbench_window", 0.0, 1.0)]) is None


def test_recorded_tpu_trace():
    # a TPU v5e trace of two annotated "searches" of small programs: the
    # events the reduction reads, flattened from its .xplane.pb
    events = [tuple(e) for e in json.loads((DATA / "tpu_probe_events.json").read_text())]
    s = reduce_events(events)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(0.346277756)
    assert 0 < s["busy_s"] < s["window_s"]
    assert set(s["module_s"]) >= {"jit__lambda", "jit_loop", "jit_scatter"}
    assert all(name.split("/")[0] in s["module_s"] for name, _ in s["device_ops"])
    assert all(label == "in_search" for label, _ in s["idle_gaps"])
    busy_by_module = sum(s["module_s"].values())
    assert s["busy_s"] <= busy_by_module + 1e-9


def test_load_events_reads_a_trace_file(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    from chipbench.profile import find_xplane

    events = load_events(find_xplane(str(tmp_path)))
    assert any(name == "chipbench_window" for _, _, name, _, _ in events)
    # the CPU has no TPU plane: nothing for the device metrics to read
    assert reduce_events(events) is None
