"""The trace reduction, on a hand-made trace whose answers are worked out
by hand, and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"

# one device, a slice from t=100 to t=200 (ns); ops at [90,120), [110,130),
# [150,160) and [195,230); host spans: a step over [100,170), a drain over
# [170,190), a wait over [190,200)
HAND = {
    "ops": [["fusion.1", 90, 30, "/device:TPU:0", ""],
            ["paged_decode_attention.1", 110, 20, "/device:TPU:0",
             ""],
            ["fusion.1", 150, 10, "/device:TPU:0", ""],
            ["copy.2", 195, 35, "/device:TPU:0", ""]],
    "modules": [["jit__step(1)", 95, 70, "/device:TPU:0"],
                ["jit__step(1)", 195, 35, "/device:TPU:0"]],
    "host": [["step", 100, 70], ["drain", 170, 20], ["wait_arrival", 190, 10],
             ["slice", 100, 100]],
}


def test_union_and_gaps():
    merged = tr.union([(90, 120), (110, 130), (150, 160)])
    assert merged == [(90, 130), (150, 160)]
    assert tr.gaps(tr.clip(merged, 100, 200), 100, 200) == [(130, 150),
                                                            (160, 200)]


def test_reduce_by_hand():
    r = tr.reduce(HAND)
    # busy inside the slice: [100,130) + [150,160) + [195,200) = 45 ns
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["devices"] == 1
    # idle: [160,195) = 35 ns under the drain (20) and the wait (5) -> drain;
    # [130,150) = 20 ns under the step
    assert r["idle_gaps"] == [["drain", pytest.approx(35e-9)],
                              ["step", pytest.approx(20e-9)]]
    # ops that start inside the slice; the first fusion starts before it
    assert dict((n, v) for n, v in r["top_ops"]) == {
        "paged_decode_attention.1": pytest.approx(20e-9),
        "fusion.1": pytest.approx(10e-9), "copy.2": pytest.approx(35e-9)}
    assert tr.op_seconds(r, "paged_decode_attention.1") == pytest.approx(20e-9)
    assert tr.module_seconds(r, "_step") == [pytest.approx(35e-9)]


def test_reduce_without_a_slice_takes_the_host_spans():
    trace = dict(HAND, host=[h for h in HAND["host"] if h[0] != "slice"])
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(100e-9)


def test_name_gap_outside_every_span():
    assert tr.name_gap((0, 10), [["step", 20, 5]]) == "other"


def test_short_name():
    assert tr.short_name("%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion("
                         "bf16[8,128]{1,0} %p), kind=kLoop") == \
        "fusion.3 bf16[8,128]"
    assert tr.short_name("%r = (bf16[32]{0:T(256)}, s32[32]{0}) fusion()") \
        == "r (bf16[32], s32[32])"
    assert tr.short_name("jit__step(12)") == "jit__step(12)"


def test_loops_count_as_busy_but_not_as_top_ops():
    trace = {"ops": [["while.1 (s32[])", 0, 100, "/device:TPU:0", ""],
                     ["fusion.2 f32[8]", 10, 20, "/device:TPU:0", ""]],
             "modules": [], "host": [["slice", 0, 200]]}
    r = tr.reduce(trace)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert [n for n, _ in r["top_ops"]] == ["fusion.2 f32[8]"]


def test_recorded_v5e_steps():
    """Two 14-layer qwen2_7b steps at 32 slots, traced on a TPU v5e: the
    slice runs from the first step's dispatch to the second's read-back."""
    trace = json.loads((DATA / "v5e_qwen2_7b_2steps.json").read_text())
    r = tr.reduce(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.205006008)
    # the two step programs, 99.2 ms each, and the host's read-back
    # between them, in which the device idles
    assert tr.module_seconds(r, "_step") == [pytest.approx(0.099198859),
                                             pytest.approx(0.099193433)]
    assert r["busy_s"] == pytest.approx(0.198391618)
    assert r["busy_s"] <= sum(tr.module_seconds(r, "_step"))
    assert r["idle_gaps"][0] == ["drain", pytest.approx(0.003450753)]
    # the paged kernel: one call a layer a step, 3.29 ms each
    kernel = [o for o in r["ops"] if o[0].startswith("paged_decode_attention")]
    assert len(kernel) == 28
    assert tr.op_seconds(r, "paged_decode_attention") == \
        pytest.approx(0.092020395)
    assert r["top_ops"][0] == ["paged_decode_attention.12 bf16[32,4,56,128]",
                               pytest.approx(0.092020395)]
    assert not any(n.startswith("while") for n, _ in r["top_ops"])
