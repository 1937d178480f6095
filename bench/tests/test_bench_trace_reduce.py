"""The reduction from trace events to busy time, program time and idle
gaps named by the host span they fell in."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"


def _events():
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("a", 1.0, 1.2), ("b", 1.1, 1.5), ("c", 2.0, 2.5),
                    ("d", 0.1, 0.3)],
            "modules": [("jit_step", 1.0, 1.5), ("jit_decode_step", 2.0,
                                                  2.5)]}},
        "spans": [(T.WINDOW_SPAN, 0.5, 3.0), ("serve.tick", 0.9, 2.8),
                  ("fabric.wait_all", 1.6, 1.9)]}


def test_busy_is_the_union_inside_the_window():
    r = T.reduce(_events())
    assert r["window_s"] == pytest.approx(2.5)
    # [1.0, 1.5] and [2.0, 2.5]; the op before the window is left out
    assert r["busy_s"] == pytest.approx(1.0)
    assert T.program_seconds(r, "decode_step") == pytest.approx(0.5)
    assert [n for n, _ in r["device_ops"]] == ["jit_step",
                                               "jit_decode_step"]


def test_idle_gaps_are_split_by_the_innermost_span():
    gaps = dict(T.reduce(_events())["idle_gaps"])
    # [0.5, 0.9] under no span, [0.9, 1.0] in the tick; [1.5, 2.0]: 0.3 s
    # inside the fabric wait (the innermost span), 0.2 s in the tick
    # around it; [2.5, 2.8] in the tick, [2.8, 3.0] under none
    assert gaps == pytest.approx({"none": 0.6, "fabric.wait_all": 0.3,
                                  "serve.tick": 0.6})


def test_union_and_gaps():
    u = T.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)], 0.5, 5.8)
    assert u == [(0.5, 3), (5, 5.8)]
    assert T.gaps(u, 0, 7) == [(0, 0.5), (3, 5), (5.8, 7)]


def test_no_window_or_device_is_an_error():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        T.reduce(ev)
    ev = _events()
    ev["devices"] = {}
    with pytest.raises(ValueError):
        T.reduce(ev)


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: three runs of a 2048 x 2048 bf16
    product between 20 ms host sleeps, each under a harness span. The
    device's clock reads about 1 ms earlier than the host's, so the
    first run falls before the window span and counts for nothing."""
    ev = T.load_json(str(DATA / "v5e_small_trace.json"))
    r = T.reduce(ev)
    assert r["window_s"] == pytest.approx(0.065993368)
    runs = ev["devices"]["/device:TPU:0"]["modules"]
    assert len(runs) == 3
    # the ops inside the last two runs cover all but a few ns of them
    assert r["busy_s"] == pytest.approx(sum(b - a for _, a, b in runs[1:]),
                                        rel=1e-3)
    assert [n for n, _ in r["device_ops"]] == [runs[0][0]]
    idle = dict(r["idle_gaps"])
    assert max(idle, key=idle.get) == "bench.host"
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
