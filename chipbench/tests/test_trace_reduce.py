"""The trace reduction on a small synthetic trace (times in ns)."""

import pytest

from chipbench import trace as tr


def _trace():
    # Window 0..100.  Chip 0 ops: 10-30, 25-40 (overlap), 60-70; chip 1
    # ops: 0-50.  Modules on chip 0: a scan (10-40) and a path-edge
    # program (60-70), and one scan module on chip 1 that starts before
    # the window ends after it is clipped (90-120 -> 90-100).
    ops0 = [("fusion.1", 10, 30), ("fusion.2", 25, 40), ("copy", 60, 70)]
    ops1 = [("fusion.1", 0, 50)]
    mods0 = [("jit__run_scan_batch(17)", 10, 40),
             ("jit__path_edge_tensor_compressed(3)", 60, 70)]
    mods1 = [("jit__run_scan_batch(17)", 90, 120)]
    spans = [("iteration", 0, 100), ("resolve", 40, 60),
             ("finalise", 70, 90)]
    return tr.Trace(modules=[mods0, mods1], ops=[ops0, ops1], spans=spans,
                    window=(0, 100))


def test_union_merges_overlaps_and_clips_to_the_window():
    t = _trace()
    assert tr.union_ns(t.ops[0], 0, 100) == 40          # 10-40 and 60-70
    assert tr.union_ns(t.ops[0], 15, 65) == 25 + 5
    assert tr.union_ns([("x", -10, 5)], 0, 100) == 5


def test_busy_and_idle_share_average_over_chips():
    t = _trace()
    assert tr.busy_s(t) == pytest.approx((40 + 50) / 2 / 1e9)
    assert tr.window_s(t) == pytest.approx(100 / 1e9)
    assert tr.idle_pct(t) == pytest.approx(55.0)
    assert tr.idle_pct(None) is None


def test_busy_is_none_without_device_operations():
    t = tr.Trace(modules=[], ops=[], spans=[], window=(0, 1))
    assert tr.busy_s(t) is None


def test_module_time_by_name_pattern():
    t = _trace()
    assert tr.module_seconds(t, r"scan") == pytest.approx((30 + 10) / 1e9)
    assert tr.module_seconds(t, r"path_edge") == pytest.approx(10 / 1e9)
    assert tr.module_seconds(t, r"waterfill") is None
    top = tr.top_modules(t)
    assert top[0] == ["jit__run_scan_batch", pytest.approx(40 / 1e9)]
    assert top[1][0] == "jit__path_edge_tensor_compressed"


def test_idle_gaps_named_by_the_innermost_span():
    t = _trace()
    gaps = tr.idle_gaps(t)
    # chip 0 idle: 0-10 (iteration), 40-60 (resolve), 70-100 (finalise
    # holds the midpoint 85)
    assert gaps == [["finalise", pytest.approx(30e-9)],
                    ["resolve", pytest.approx(20e-9)],
                    ["iteration", pytest.approx(10e-9)]]
    t.spans = []
    assert tr.idle_gaps(t)[0][0] == "untraced"


def test_ops_count_in_the_module_they_start_in_on_their_own_chip():
    t = _trace()
    # chip 0's fusions start inside its scan module; chip 1's fusion
    # starts before chip 1's scan module and does not count
    assert tr.op_seconds_in_modules(t, r"^fusion", r"scan") == \
        pytest.approx((20 + 15) / 1e9)
    assert tr.op_seconds_in_modules(t, r"^copy", r"path_edge") == \
        pytest.approx(10 / 1e9)
    assert tr.op_seconds_in_modules(t, r"^fusion", r"path_edge") is None
    assert tr.op_seconds_in_modules(t, r"^copy", r"scan") is None
