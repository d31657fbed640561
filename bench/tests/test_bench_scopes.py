"""The reduction of device time to program phases (``harness.scopes``):
the phase of an ``op_name``, the op names read from a module's HLO text,
and, on a small trace recorded on a TPU v5e with the round programs'
names beside it, that every op lands in one phase, that the phases add up
to the busy time, and that the existing readers read the same with the
program's host spans in the trace."""
from types import SimpleNamespace

import pytest

from harness import scopes, spec
from harness import trace as tr

E = tr.Event
STEP = "jit(round_step)/vmap(hcef.local_step)/while/body/closed_call"


@pytest.mark.parametrize("op_name,phase", [
    (f"{STEP}/jvp(lm.embed)/gather", "vocab"),
    (f"{STEP}/jvp(lm.head)/dot_general", "vocab"),
    (f"{STEP}/transpose(jvp(lm.head))/dot_general", "vocab"),
    (f"{STEP}/jvp()/while/body/closed_call/lm.attn/pallas_call", "local_fwd"),
    (f"{STEP}/jvp()/while/body/closed_call/lm.mlp/dot_general", "local_fwd"),
    (f"{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/"
     f"rematted_computation/lm.attn/pallas_call", "local_bwd"),
    (f"{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/lm.mlp/mul",
     "local_bwd"),
    (f"{STEP}/transpose(jvp())/add_any", "local_bwd"),
    ("jit(round_step)/vmap(hcef.local_step)/while", "local_fwd"),
    (f"{STEP}/hcef.sgd/convert_element_type", "optimizer"),
    (f"{STEP}/hcef.grad_stats/reduce_sum", "optimizer"),
    ("jit(round_step)/vmap(hcef.grad_stats)/jit(_bernoulli)/lt", "optimizer"),
    ("jit(round_step)/vmap(hcef.delta)/convert_element_type", "delta"),
    ("jit(round_step)/hcef.compress/pallas_call", "compress"),
    ("jit(round_step)/hcef.aggregate/dot_general", "aggregate"),
    ("jit(round_step)/shard_map/hcef.gossip/ppermute", "aggregate"),
    ("state.params['emb']", "unscoped"),
    ("jit(round_step)/add", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of_an_op_name(op_name, phase):
    assert scopes.phase_of(op_name) == phase


HLO = """HloModule jit_round_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[4,896]) -> bf16[4,896] {
  %param_0 = bf16[4,896]{1,0} parameter(0)
  %bitcast.1 = bf16[4,896]{1,0} bitcast(%param_0)
  ROOT %convert.2 = bf16[4,896]{1,0} convert(%bitcast.1), metadata={op_name="jit(round_step)/vmap(hcef.local_step)/while/body/closed_call/hcef.sgd/convert_element_type"}
}

%async_computation.3 (param_0.1: bf16[2,8]) -> bf16[1,8] {
  %param_0.1 = bf16[2,8]{1,0} parameter(0)
  ROOT %slice.4 = bf16[1,8]{1,0} slice(%param_0.1), slice={[0:1], [0:8]}
}

ENTRY %main.5 (p: bf16[4,896], q: bf16[2,8]) -> bf16[4,896] {
  %p = bf16[4,896]{1,0} parameter(0), metadata={op_name="state.params['emb']"}
  %q = bf16[2,8]{1,0} parameter(1)
  %fusion.6 = bf16[4,896]{1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %copy.7 = bf16[4,896]{1,0} copy(%fusion.6)
  %copy.8 = bf16[4,896]{1,0} copy(%p)
  %slice-start.9 = ((bf16[2,8]{1,0}), bf16[1,8]{1,0}, s32[]{:S(2)}) async-start(%q), calls=%async_computation.3
  %lm.attn.10 = bf16[4,2,14,640,64]{4,3,2,1,0} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_step)/vmap(hcef.local_step)/while/body/closed_call/hcef.local_step/jvp()/while/body/closed_call/lm.attn/pallas_call" stack_frame_id=7}
  ROOT %add.11 = s32[] add(%q, %q), metadata={op_name="jit(round_step)/add"}
}
"""


def test_hlo_op_names_resolves_what_the_compiler_made():
    m = scopes.hlo_op_names(HLO)
    # ops that run; those of the fused and async computations do not
    assert set(m) == {"p", "q", "fusion.6", "copy.7", "copy.8",
                      "slice-start.9", "lm.attn.10", "add.11"}
    assert m["lm.attn.10"][0] == "bf16[4,2,14,640,64]"
    assert m["lm.attn.10"][2] == "custom-call"
    assert m["slice-start.9"][0] == "((bf16[2,8]"
    # a fusion with no op_name takes its fused root's
    assert scopes.phase_of(m["fusion.6"][1]) == "optimizer"
    # a copy takes its operand's: a named fusion's, or a parameter's
    assert m["copy.7"][1] == m["fusion.6"][1]
    assert m["copy.8"][1] == "state.params['emb']"
    assert scopes.phase_of(m["copy.8"][1]) == "unscoped"
    assert scopes.phase_of(m["lm.attn.10"][1]) == "local_fwd"


def test_event_key_reads_the_device_planes_names():
    assert scopes.event_key(
        "%fusion.620 = (bf16[4,896]{1,0:T(4,128)(2,1)}, f32[4]{0}) "
        "fusion(bf16[4,896]{1,0} %p), kind=kLoop") == ("fusion.620",
                                                       "(bf16[4,896]")
    assert scopes.event_key("%slice-start.90 = ((bf16[4,8,896,896]{3,2,1,0}"
                            "), bf16[1,8,896,896]{3,2,1,0}, s32[]) "
                            "async-start(...)") == ("slice-start.90",
                                                   "((bf16[4,8,896,896]")


# --- a small trace recorded on a TPU v5e: slices of a traced window of
# qwen0.5b-fl-thmin, one per phase, with the op names of the round
# programs' instructions that run in them ---

@pytest.fixture(scope="module")
def recorded():
    d = spec.load_json(spec.BENCH_DIR / "tests" / "data"
                       / "trace_v5e_scopes.json")
    names = d["op_names"]
    maps = {prog: {k: (v[0], names[v[1]], v[2]) for k, v in m.items()}
            for prog, m in d["programs"].items()}
    return [E(**e) for e in d["events"]], d["windows"], maps


PLANE = "/device:TPU:0"


def test_recorded_module_is_read_with_its_own_program(recorded):
    ev, windows, maps = recorded
    mod = tr.select(ev, plane=PLANE, line=tr.MODULES_LINE)[0]
    ops = [e for e in tr.select(ev, plane=PLANE, line=tr.OPS_LINE)
           if mod.start_ns <= e.start_ns and e.end_ns <= mod.end_ns]
    # the gossip program numbers its fusions otherwise: same names, other
    # results
    assert scopes._best_map(ops, [maps["gossip"], maps["intra"]]) \
        is maps["intra"]
    assert scopes._best_map(ops, [maps["gossip"]]) is None


def test_recorded_ops_each_land_in_one_phase(recorded):
    ev, windows, maps = recorded
    seen = set()
    for name, (t0, t1) in windows.items():
        ph = scopes.phase_ns(ev, PLANE, t0, t1, list(maps.values()))
        ops = [e for e in tr.select(ev, plane=PLANE, line=tr.OPS_LINE)
               if e.end_ns > t0 and e.start_ns < t1]
        busy = tr.busy_ns(ops, t0, t1)
        assert sum(ph.ns.values()) == pytest.approx(busy, rel=0.01), name
        assert ph.ns[name] > 0, (name, ph.ns)
        seen |= {k for k, v in ph.ns.items() if v > 0}
    assert set(scopes.PHASES) - {"unscoped"} <= seen


def test_recorded_transposed_op_lands_in_local_bwd(recorded):
    ev, windows, maps = recorded
    intra = maps["intra"]
    ops = tr.select(ev, plane=PLANE, line=tr.OPS_LINE)
    on = lambda e: intra.get(scopes.event_key(e.name)[0], ("", ""))[1]
    leaf = lambda e: not any(o is not e and e.start_ns <= o.start_ns
                             and o.end_ns <= e.end_ns for o in ops)
    bwd = [e for e in ops
           if "lm.attn" in on(e) and "transpose(" in on(e) and leaf(e)]
    assert bwd
    e = bwd[0]
    ph = scopes.phase_ns(ev, PLANE, e.start_ns, e.end_ns, [intra])
    assert ph.ns["local_bwd"] == pytest.approx(e.dur_ns)


def _ctx(ev, window, config):
    from harness import counts, device
    return SimpleNamespace(
        kind="fl", chips=1, counts=counts, rounds=1,
        peaks=device.peaks_for("TPU v5 lite"), config=config,
        traffic=spec.load_json(spec.traffic_path("fl_r4_thmin")),
        tokens=16384, window_s=0.636, host_gaps_s=[0.007, 0.0072],
        trace=SimpleNamespace(events=ev, t0=window[0], t1=window[1],
                              planes=[PLANE]))


EXISTING = ("device_idle_share.train", "flash_fwd_roofline.train",
            "topk_compress_roofline.train", "host_gap_ms.train",
            "train_mfu")


def _with_program_spans(ev, t0, t1):
    mid = (t0 + t1) / 2
    return ev + [E(tr.HOST_PLANE, "python", "hcef.round", t0, t1 - t0),
                 E(tr.HOST_PLANE, "python", "hcef.controller", t0, mid - t0),
                 E(tr.HOST_PLANE, "python", "hcef.reports", t0, 10.0)]


def test_existing_readers_read_the_same_with_program_spans(recorded):
    ev, windows, _ = recorded
    qwen = spec.load_json(spec.BENCH_DIR / "configs" / "qwen2.5-0.5b_l8.json")
    old = spec.load_json(spec.BENCH_DIR / "tests" / "data"
                      / "trace_v5e_slices.json")
    granite = dict(qwen, hidden_size=1024, num_attention_heads=16,
                   num_key_value_heads=8)  # the older trace's attention
    cases = [(ev, w, qwen) for w in windows.values()]
    cases += [([E(**e) for e in old["events"]], w, granite)
              for w in old["windows"].values()]
    read = 0
    for events, w, config in cases:
        for name in EXISTING:
            reader = spec.load_reader(name)
            a = reader(_ctx(events, w, config))
            b = reader(_ctx(_with_program_spans(events, *w), w, config))
            assert a == b, name
            read += a is not None
    assert read > len(cases)


def test_idle_gaps_name_the_innermost_span():
    host = [E(tr.HOST_PLANE, "python", "bench.window", 0, 100),
            E(tr.HOST_PLANE, "python", "bench.controller", 10, 20),
            E(tr.HOST_PLANE, "python", "hcef.controller", 12, 10)]
    got = dict(tr.attribute_gaps([(10, 30)], host))
    assert got == pytest.approx({"hcef.controller": 10e-9,
                                 "bench.controller": 10e-9})


def test_phase_readers_read_the_recorded_phases(recorded, monkeypatch):
    ev, windows, maps = recorded
    monkeypatch.setattr(scopes, "fl_round_maps",
                        lambda ctx: list(maps.values()))
    t0 = min(w[0] for w in windows.values())
    t1 = max(w[1] for w in windows.values())
    ctx = _ctx(ev, (t0, t1), {})
    ph = scopes.phase_ns(ev, PLANE, t0, t1, list(maps.values()))
    for phase in scopes.PHASES[:-1]:
        got = spec.load_reader(f"{phase}_ms.train")(ctx)
        assert got == pytest.approx(ph.ns[phase] * 1e-6)


def test_phase_readers_read_nothing_without_scopes(recorded, monkeypatch):
    """A program without scopes (the parent of the PR that added them):
    its ops name no phase, and the readers return None."""
    ev, windows, maps = recorded
    bare = [{k: (v[0], "", v[2]) for k, v in m.items()}
            for m in maps.values()]
    monkeypatch.setattr(scopes, "fl_round_maps", lambda ctx: bare)
    w = next(iter(windows.values()))
    for phase in scopes.PHASES[:-1]:
        assert spec.load_reader(f"{phase}_ms.train")(_ctx(ev, w, {})) \
            is None
    no_trace = _ctx(ev, w, {})
    no_trace.trace = None
    assert spec.load_reader("vocab_ms.train")(no_trace) is None


@pytest.mark.parametrize("op_name,scope,inside", [
    (f"{STEP}/jvp()/while/body/closed_call/lm.attn/pallas_call", "lm.attn",
     True),
    (f"{STEP}/transpose(jvp(lm.head))/dot_general", "lm.head", True),
    ("jit(round_step)/vmap(hcef.delta)/convert_element_type", "hcef.delta",
     True),
    ("hcef.compress", "hcef.compress", True),
    (f"{STEP}/hcef.local_step/jvp()/lm.attn/dot_general", "hcef.local_step",
     True),
    (f"{STEP}/jvp()/lm.attention/dot_general", "lm.attn", False),
    (f"{STEP}/jvp()/xlm.attn/dot_general", "lm.attn", False),
    (f"{STEP}/jvp()/lm.mlp/dot_general", "lm.attn", False),
    ("", "lm.attn", False),
])
def test_an_op_name_is_in_a_scope_by_its_path(op_name, scope, inside):
    assert scopes.in_scope(op_name, scope) is inside


def _whole_window(recorded, monkeypatch):
    """A ctx over all the recorded slices, and the list of the times the
    round programs were compiled for their names."""
    ev, windows, maps = recorded
    compiled = []

    def round_maps(ctx):
        compiled.append(ctx)
        return list(maps.values())

    monkeypatch.setattr(scopes, "fl_round_maps", round_maps)
    t0 = min(w[0] for w in windows.values())
    t1 = max(w[1] for w in windows.values())
    return _ctx(ev, (t0, t1), {}), compiled


def test_scope_ms_reads_the_recorded_scopes(recorded, monkeypatch):
    ctx, compiled = _whole_window(recorded, monkeypatch)
    ph = scopes.phase_ms(ctx)
    compress = scopes.scope_ms(ctx, "hcef.compress")
    assert compress == {"fwd": ph["compress"], "bwd": 0.0}
    attn = scopes.scope_ms(ctx, "lm.attn")
    assert attn["fwd"] > 0 and attn["bwd"] > 0
    assert attn["fwd"] + attn["bwd"] <= ph["local_fwd"] + ph["local_bwd"]
    head = scopes.scope_ms(ctx, "lm.head")
    assert 0 < head["fwd"] + head["bwd"] <= ph["vocab"]
    assert scopes.scope_ms(ctx, "lm.nosuch") is None
    # the phases still sum to the busy time
    v = ctx.trace
    busy = tr.busy_ns(tr.select(v.events, plane=PLANE, line=tr.OPS_LINE),
                      v.t0, v.t1)
    assert sum(ph.values()) == pytest.approx(busy * 1e-6, rel=1e-9)
    # one naming of the window's ops serves every reader
    assert len(compiled) == 1


def test_scope_ops_are_the_window_ops_under_the_scope(recorded, monkeypatch):
    ctx, compiled = _whole_window(recorded, monkeypatch)
    ops = scopes.scope_ops(ctx, "lm.attn")
    assert ops and all(scopes.in_scope(on, "lm.attn") for _, _, on in ops)
    v = ctx.trace
    assert all(e.plane == PLANE and own > 0 and e.end_ns > v.t0
               and e.start_ns < v.t1 for e, own, _ in ops)
    assert {scopes.phase_of(on) for _, _, on in ops} == {"local_fwd",
                                                         "local_bwd"}
    ms = scopes.scope_ms(ctx, "lm.attn")
    assert sum(own for _, own, _ in ops) * 1e-6 == pytest.approx(
        ms["fwd"] + ms["bwd"])
    assert len(compiled) == 1


def test_scope_readings_need_a_trace(recorded):
    ev, windows, _ = recorded
    ctx = _ctx(ev, next(iter(windows.values())), {})
    ctx.trace = None
    assert scopes.scope_ms(ctx, "lm.attn") is None
    assert scopes.scope_ops(ctx, "lm.attn") is None
