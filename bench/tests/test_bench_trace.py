"""The trace reduction: busy/idle union, kernel time by name, exposed
collective time and idle-gap attribution, on hand-made intervals with
known answers and on a small trace recorded on a TPU v5e."""
import pytest

from harness import trace as tr

E = tr.Event


def ops(*spans, plane="/device:TPU:0", line=tr.OPS_LINE, name="op"):
    return [E(plane, line, name, a, b - a) for a, b in spans]


def test_union_busy_and_gaps():
    ev = ops((0, 10), (5, 20), (30, 40), (38, 45), (50, 60))
    assert tr.union([(e.start_ns, e.end_ns) for e in ev]) == [
        (0, 20), (30, 45), (50, 60)]
    assert tr.busy_ns(ev, 0, 100) == 45
    assert tr.busy_ns(ev, 10, 55) == 10 + 15 + 5
    assert tr.gaps(ev, 0, 100) == [(20, 30), (45, 50), (60, 100)]
    assert tr.gaps(ev, -5, 60) == [(-5, 0), (20, 30), (45, 50)]


def test_exposed_collective_time():
    compute = ops((0, 10), (15, 30), (40, 50))
    coll = ops((5, 20), (25, 45), name="collective-permute")
    # 5..20: hidden 5..10 and 15..20 -> exposed 5; 25..45: hidden 25..30
    # and 40..45 -> exposed 10
    assert tr.exposed_ns(coll, compute, 0, 100) == 15
    assert tr.exposed_ns(coll, compute, 0, 12) == 2  # 10..12
    assert tr.exposed_ns(coll, [], 0, 100) == 35


def test_op_totals_and_gap_attribution():
    ev = (ops((0, 10), (20, 25), name="fusion.1")
          + ops((10, 12), name="custom-call.3"))
    got = tr.op_totals(ev, 0, 100)
    assert [n for n, _ in got] == ["fusion.1", "custom-call.3"]
    assert [s for _, s in got] == pytest.approx([15e-9, 2e-9])
    host = [E(tr.HOST_PLANE, "python", "bench.window", 0, 100),
            E(tr.HOST_PLANE, "python", "bench.controller", 12, 6)]
    got = dict(tr.attribute_gaps(tr.gaps(ev, 0, 30), host))
    # gap 12..20: 12..18 inside the controller span, 18..20 only in the
    # window; gap 25..30 in the window
    assert got == pytest.approx({"bench.controller": 6e-9,
                                 "bench.window": 7e-9})


def test_select_by_plane_line_and_name():
    ev = ops((0, 1), name="a") + ops((0, 1), plane="/device:TPU:1",
                                     name="b")
    ev += ops((0, 1), line="XLA Modules", name="jit_step")
    assert [e.name for e in tr.select(ev, line=tr.OPS_LINE)] == ["a", "b"]
    assert tr.device_planes(ev) == ["/device:TPU:0", "/device:TPU:1"]
    assert [e.name for e in tr.select(ev, name="^jit_")] == ["jit_step"]


# --- a small trace recorded on a TPU v5e (two slices of a traced window of
# an fl round of Granite 3.0 1B-A400M cut to 3 layers, 4 clients, seq 512):
# one flash-attention forward launch and one top-k launch with the ops
# around them ---

# the attention sizes of the recorded model (16 heads of 64, 8 KV heads)
RECORDED = {"hidden_size": 1024, "num_attention_heads": 16,
            "num_key_value_heads": 8,
            "param_dtype": "bfloat16"}

@pytest.fixture(scope="module")
def recorded():
    from harness import spec
    d = spec.load_json(spec.BENCH_DIR / "tests" / "data"
                       / "trace_v5e_slices.json")
    return [tr.Event(**e) for e in d["events"]], d["windows"]


def _brute_busy(ops, t0, t1):
    import numpy as np
    t0i = int(t0)
    mark = np.zeros(int(t1) - t0i, bool)
    for e in ops:
        a, b = max(int(round(e.start_ns)), t0i), min(int(round(e.end_ns)),
                                                     int(t1))
        if b > a:
            mark[a - t0i:b - t0i] = True
    return float(mark.sum())


def test_recorded_busy_matches_a_brute_force_timeline(recorded):
    ev, windows = recorded
    for t0, t1 in windows.values():
        ops = [e for e in ev if e.end_ns >= t0 and e.start_ns <= t1]
        busy = tr.busy_ns(ops, t0, t1)
        assert busy == pytest.approx(_brute_busy(ops, t0, t1), abs=len(ops))
        idle = tr.length(tr.gaps(ops, t0, t1))
        assert busy + idle == pytest.approx(t1 - t0)


def _ctx(ev, window):
    from types import SimpleNamespace
    from harness import counts, device, spec
    return SimpleNamespace(
        kind="fl", chips=1, counts=counts,
        peaks=device.peaks_for("TPU v5 lite"),
        config=RECORDED,
        traffic=spec.load_json(spec.traffic_path("fl_r4_thmin")),
        trace=SimpleNamespace(events=ev, t0=window[0], t1=window[1],
                              planes=["/device:TPU:0"]))


def test_recorded_kernel_rooflines_by_hand(recorded):
    from harness import spec
    ev, windows = recorded
    kernels = [e for e in ev if tr.is_kernel(e)]
    flash = [e for e in kernels if tr.result_dims(e.name)[-1] == 64]
    topk = [e for e in kernels if tr.result_dims(e.name)[-1] == 1024]
    assert tr.result_dims(flash[0].name) == (4, 2, 16, 640, 64)
    # 8 sequences of 513 tokens, 16 heads of 64, 8 KV heads: bytes bound
    # (25,214,976 B at 819 GB/s) above FLOPs (4,320,165,888 at 197 TF/s)
    t_min = 8 * 2 * 513 * 64 * (2 * 16 + 2 * 8) / 819e9
    got = spec.load_reader("flash_fwd_roofline.train")(
        _ctx(ev, windows["flash"]))
    assert got == pytest.approx(100 * t_min / (flash[0].dur_ns * 1e-9))
    assert 3.0 < got < 4.0
    # 4 x 24 x 128 x 1024 coordinates, 2 bytes each, read twice and
    # written twice
    w = windows["topk"]
    inside = [e for e in topk if w[0] <= e.start_ns and e.end_ns <= w[1]]
    t_min = 8 * 4 * 24 * 128 * 1024 / 819e9
    got = spec.load_reader("topk_compress_roofline.train")(_ctx(ev, w))
    assert got == pytest.approx(
        100 * t_min / (sum(e.dur_ns for e in inside) * 1e-9))


def test_self_times_nest(recorded):
    ev = ops((0, 100), name="while") + ops((10, 30), (40, 50), name="f")
    st = {e.start_ns: own for e, own in tr.self_times(ev, 0, 100)}
    assert st == {0: 70, 10: 20, 40: 10}
    assert tr.op_totals(ev, 0, 100)[0][1] == pytest.approx(70e-9)
