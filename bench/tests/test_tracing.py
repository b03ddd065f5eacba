"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (``data/trace_v5e.json``)."""
from pathlib import Path

import pytest

import tracing

MS = 1_000_000  # ns


def _trace():
    # window [0, 100) ms; device ops on one chip; host spans nest
    return dict(
        device={"/device:TPU:0": [
            ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10 * MS, 10 * MS],
            ["%gains_pallas.2 = f32[8,128]{1,0} custom-call(s32[8]{0} %a)",
             15 * MS, 10 * MS],                     # 15-25 (overlaps)
            ["%while.3 = (s32[]) while((s32[]) %t)", 40 * MS, 20 * MS],
            ["%sort.4 = s32[8]{0:T(1024)S(1)} sort(s32[8]{0} %w)",
             45 * MS, 5 * MS],                      # inside the while
            ["%pins_count_pallas = s32[8]{0} custom-call(s32[8]{0} %gains_pallas.2)",
             90 * MS, 20 * MS],                     # 90-110, clipped
            ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", -5 * MS, 10 * MS],
        ]},
        modules={"/device:TPU:0": [["jit_step(123)", -5 * MS, 70 * MS],
                                   ["jit_pins(9)", 90 * MS, 20 * MS]]},
        host=[["bench_window", 0, 100 * MS],
              ["partition", 0, 100 * MS],
              ["coarsen", 0, 30 * MS],
              ["refine", 30 * MS, 70 * MS],
              ["refine_level", 30 * MS, 20 * MS]])


def test_busy_union_and_idle():
    t = _trace()
    assert tracing.window_bounds(t) == (0, 100 * MS)
    # busy: 0-5, 10-25, 40-60, 90-100 -> 50 ms
    assert tracing.busy_s(t) == pytest.approx(0.050)


def test_kernel_time_and_top_ops():
    t = _trace()
    assert tracing.op_time(t, tracing.is_kernel) == (2, pytest.approx(0.020))
    n, s = tracing.op_time(t, lambda x: tracing.is_kernel(x, "gains"))
    assert (n, s) == (1, pytest.approx(0.010))
    # by self time: the while's 20 ms less its nested 5 ms sort
    top = dict(tracing.top_ops(t))
    assert top == pytest.approx({
        "jit_step %while.3 while": 0.015, "jit_step %fusion.1 fusion": 0.010,
        "jit_step %gains_pallas.2 custom-call": 0.010,
        "jit_pins %pins_count_pallas custom-call": 0.010,
        "jit_step %sort.4 sort": 0.005})
    assert tracing.top_ops(t, 1) == [["jit_step %while.3 while",
                                      pytest.approx(0.015)]]


@pytest.mark.parametrize("name,kernel", [
    ("%gains_pallas = f32[8]{0} custom-call()", "gains"),
    ("%gains_pallas.12 = f32[8]{0} custom-call()", "gains"),
    ("%pair_scores_pallas.1 = (f32[8]{0}) custom-call()", "pair_scores"),
    ("%pins_count_pallas.7 = (s32[8]{0}) custom-call()", "pins_count"),
])
def test_kernel_names(name, kernel):
    assert tracing.is_kernel(name) and tracing.is_kernel(name, kernel)
    others = set(tracing.KERNELS) - {kernel}
    assert not any(tracing.is_kernel(name, k) for k in others)


def test_operands_do_not_make_a_kernel():
    assert not tracing.is_kernel(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %gains_pallas.2)")
    assert not tracing.is_kernel("%gains_pallas_x.1 = f32[8]{0} copy()")


def test_idle_gaps_by_innermost_host_span():
    gaps = dict(tracing.idle_gaps(_trace()))
    # a gap goes whole to the span open at its midpoint: 5-10 in
    # coarsen, 25-40 in refine_level (midpoint 32.5), 60-90 in refine
    assert gaps == pytest.approx(dict(coarsen=0.005, refine_level=0.015,
                                      refine=0.030))


def test_no_window_is_an_error():
    t = _trace()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        tracing.busy_s(t)


def test_recorded_v5e_trace():
    t = tracing.load(str(Path(__file__).parent / "data" / "trace_v5e.json"))
    t0, t1 = tracing.window_bounds(t)
    # busy by brute force over 100 ns ticks
    ticks = set()
    for _, s, d in t["device"]["/device:TPU:0"]:
        a, b = max(s, t0), min(s + d, t1)
        ticks.update(range(int((a - t0) // 100), int((b - t0 + 99) // 100)))
    assert tracing.busy_s(t) == pytest.approx(len(ticks) * 1e-7, rel=1e-3)
    assert tracing.busy_s(t) <= (t1 - t0) / 1e9
    gains = tracing.op_time(t, lambda n: tracing.is_kernel(n, "gains"))
    pins = tracing.op_time(t, lambda n: tracing.is_kernel(n, "pins_count"))
    assert gains == (1, pytest.approx(0.001704705))
    assert pins == (1, pytest.approx(3.2091e-05))
    assert tracing.op_time(t, tracing.is_kernel)[0] == 2
    top = tracing.top_ops(t, 3)
    assert top[0][0].endswith("%gains_pallas.1 custom-call")
    assert top[0][1] >= top[1][1] >= top[2][1]
    assert {n for n, _ in tracing.idle_gaps(t)} <= {"refine_level", "idle"}
