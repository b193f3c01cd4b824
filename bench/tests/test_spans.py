"""The program-span reduction (``bench/spans.py``) on a hand-made trace
whose answers are worked out by hand, on three steps recorded on a TPU
v5e, and the tool's harness at smoke widths on the CPU."""
import json
from pathlib import Path

import pytest

from bench import spans as sp

DATA = Path(__file__).resolve().parent / "data"

# One device, a slice over [0, 200) ns. Two step programs on the device,
# [10, 60) and [120, 170), so the device idles over [0, 10), [60, 120)
# and [170, 200): 100 ns.
# Step 1: the harness's ``step`` [0, 30) holds engine.step [1, 29):
# admit [2, 6) (store.lookup [3, 5)), feed [6, 8), launch [8, 9), publish
# [20, 25) (store.insert [21, 24)). Its ``drain`` [30, 70) holds a
# readback [31, 65) that waits for the program's end at 60. Nothing
# covers [70, 80). A ``submit`` [80, 95) holds engine.submit [81, 94)
# (store.register [82, 92)).
# Step 2: ``step`` [95, 130) holds engine.step [96, 128): admit [97, 100),
# launch [110, 115). Its ``drain`` [130, 190) holds a readback [132,
# 178); a ``wait_arrival`` [190, 200) ends the slice.
DEV = "/device:TPU:0"
HAND = {
    "ops": [["fusion.1", 10, 50, DEV, ""], ["fusion.1", 120, 50, DEV, ""]],
    "modules": [["jit__step(1)", 10, 50, DEV],
                ["jit__step(1)", 120, 50, DEV]],
    "host": [["step", 0, 30], ["drain", 30, 40], ["submit", 80, 15],
             ["step", 95, 35], ["drain", 130, 60],
             ["wait_arrival", 190, 10],
             ["slice", 0, 200]],
    "spans": [["engine.step", 1, 28, {"n": 0}],
              ["engine.admit", 2, 4, {}], ["store.lookup", 3, 2, {}],
              ["engine.feed", 6, 2, {"S": 8, "NW": 20}],
              ["engine.launch", 8, 1, {"S": 8, "NW": 20}],
              ["engine.publish", 20, 5, {}], ["store.insert", 21, 3, {}],
              ["engine.readback", 31, 34, {}],
              ["engine.submit", 81, 13, {}], ["store.register", 82, 10, {}],
              ["engine.step", 96, 32, {"n": 1}],
              ["engine.admit", 97, 3, {}],
              ["engine.launch", 110, 5, {"S": 1, "NW": 20}],
              ["engine.readback", 132, 46, {}]],
}


def test_idle_by_span_by_hand():
    out = sp.analyse(HAND)
    assert out["idle_s"] == pytest.approx(100e-9)
    assert out["programs"] == 2 and out["step_ms"] == pytest.approx(50e-6)
    # each idle instant under its innermost span:
    # [0,10): harness step 1, engine.step 1+1, admit 1+1, lookup 2,
    #   feed 2, launch 1;
    # [60,120): readback 5, harness drain 5, other 10, harness submit
    #   1+1, engine.submit 1+2, store.register 10, harness step 1,
    #   engine.step 1+10+5, admit 3, launch 5;
    # [170,200): readback 8, harness drain 12, harness wait_arrival 10
    want = {"engine.step": 18, "harness:drain": 17, "engine.readback": 13,
            "other": 10, "store.register": 10, "harness:wait_arrival": 10,
            "engine.launch": 6, "engine.admit": 5, "engine.submit": 3,
            "store.lookup": 2, "engine.feed": 2, "harness:step": 2,
            "harness:submit": 2}
    got = dict(out["idle_by_span"])
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert out["idle_by_span"][0][0] == "engine.step"
    # under a program span: 18+13+10+6+5+3+2+2 = 59 of 100
    assert out["program_share"] == pytest.approx(0.59)
    # the longest gap, [60,120), is named by the harness span that
    # overlaps it most (step 25, submit 15, drain 10); the program held
    # it in engine.step for 1+10+5 ns
    name, dur, split = out["gaps"][0]
    assert (name, dur) == ("step", pytest.approx(60e-9))
    assert split[0] == ["engine.step", pytest.approx(16e-9)]


def test_step_phases_by_hand():
    out = sp.analyse(HAND)["steps"]
    assert out["steps"] == 2 and out["spans"] == 7
    # pre-launch: 9-1 = 8 and 115-96 = 19, 13.5 ns a step
    assert out["prelaunch_ms"] == pytest.approx(13.5e-6)
    # store: lookup 2 + insert 3 + register 10, 7.5 ns a step
    assert out["store_host_ms"] == pytest.approx(7.5e-6)
    # launch to program: 10-9 = 1 and 120-115 = 5, 3 ns a step
    assert out["launch_wait_ms"] == pytest.approx(3e-6)
    # program end to read-back end: 65-60 = 5 and 178-170 = 8
    assert out["readback_wait_ms"] == pytest.approx(6.5e-6)
    assert out["span_ms"]["engine.step"] == pytest.approx(30e-6)
    assert sp.step_phases([], []) is None


def test_a_program_begun_before_its_call_returned_waits_nothing():
    spans = [["engine.step", 0, 20, {}], ["engine.launch", 5, 10, {}],
             ["engine.readback", 20, 30, {}]]
    out = sp.step_phases(spans, [["jit__step(1)", 8, 30, DEV]])
    assert out["launch_wait_ms"] == 0
    assert out["prelaunch_ms"] == pytest.approx(15e-6)
    assert out["readback_wait_ms"] == pytest.approx(12e-6)


def test_labels_cover_the_window_and_prefer_program_spans():
    pieces = sp.labels([["engine.step", 10, 20, {}]],
                       [["step", 5, 30]], 0, 50)
    assert pieces == [(0, 5, "other"), (5, 10, "harness:step"),
                      (10, 30, "engine.step"), (30, 35, "harness:step"),
                      (35, 50, "other")]


def test_recorded_v5e_steps_with_spans():
    """Three 14-layer qwen2_7b steps at 32 slots of the
    ``qwen2_7b.sessions`` cell, traced on a TPU v5e with the program's
    spans (``bench/spans.py --save``): one step finishes a request, whose
    ``store.complete`` holds the device idle for 14 ms."""
    tr = json.loads((DATA / "v5e_qwen2_7b_3steps_spans.json").read_text())
    out = sp.analyse(tr)
    assert out["window_s"] == pytest.approx(0.323242902)
    assert out["busy_s"] == pytest.approx(0.297594681)
    assert out["programs"] == 3
    assert out["step_ms"] == pytest.approx(99.19855766666667)
    assert out["idle_by_span"][0] == ["store.complete",
                                      pytest.approx(0.013999738)]
    assert out["program_share"] == pytest.approx(0.9643484045150734)
    assert out["gaps"][0][:2] == ["step", pytest.approx(0.018654903)]
    steps = out["steps"]
    assert steps["steps"] == 3
    assert steps["prelaunch_ms"] == pytest.approx(1.82419)
    assert steps["store_host_ms"] == pytest.approx(4.777456)
    assert steps["launch_wait_ms"] == pytest.approx(0.245005)
    assert steps["readback_wait_ms"] == pytest.approx(1.1960686666666667)
    launches = [s for s in tr["spans"] if s[0] == "engine.launch"]
    assert [s[3]["step"] for s in launches] == [1178, 1179, 1180]
    assert all(s[3]["S"] == 8 and s[3]["NW"] == 128 and s[3]["fed"] == 32
               for s in launches)
    finish = [s for s in tr["spans"] if s[0] == "engine.finish"]
    complete = [s for s in tr["spans"] if s[0] == "store.complete"]
    assert complete and all(any(sp._inside(c, f) for f in finish)
                            for c in complete)


def test_span_harness_at_smoke_widths(tmp_path):
    """The tool's harness on the CPU: the slice's trace keeps the
    program's spans and the engine leaves the slice untraced."""
    from bench.tests.smoke import smoke_files
    _, cell, config, mix = smoke_files()
    h = sp.SpanHarness(cell, config, mix, 2 ** 31 + 5, 3.0, True,
                       require_tpu=False)
    h.serve()
    assert h.eng.trace is None
    names = {s[0] for s in h.slice_trace["spans"]}
    assert {"engine.step", "engine.launch", "engine.readback",
            "store.lookup"} <= names
    out = sp.analyse(h.slice_trace)
    assert out["steps"]["steps"] > 0
    assert 0 < out["program_share"] <= 1
    assert sum(v for _, v in out["idle_by_span"]) == \
        pytest.approx(out["idle_s"])
    ex = sp.excerpt(h.slice_trace, 1)
    assert sp.analyse(ex)["steps"]["steps"] == 3
    cost = sp.span_cost(100, tmp_path)
    assert cost["pairs"] == 100 and cost["on_us"] > 0
