"""Device time by program phase.

The program names its phases with ``jax.named_scope`` (``lm.*`` in
``models/lm.py``, ``hcef.*`` in ``core/``); XLA keeps the names in each
instruction's ``op_name``.  The device planes of a TPU trace name an op by
its HLO instruction and drop that metadata, so the names are taken from
the optimized HLO text of the programs the window ran, keyed by
instruction name, module by module (the intra and gossip programs number
their fusions differently).

Each op of chip 0 in the window gives its self time
(``trace.self_times``) to exactly one phase, by the innermost scope of its
``op_name``:

- ``vocab``: ``lm.embed`` or ``lm.head``, forward and backward;
- ``local_fwd`` / ``local_bwd``: other ops under ``hcef.local_step``
  (``lm.attn``, ``lm.mlp``, the norms and residuals), outside / inside an
  autodiff ``transpose(...)``; the backward holds the remat re-run of the
  forward (``rematted_computation``), also counted on its own;
- ``optimizer``: ``hcef.sgd`` and ``hcef.grad_stats``;
- ``delta``: ``hcef.delta``; ``compress``: ``hcef.compress``;
- ``aggregate``: ``hcef.aggregate`` and ``hcef.gossip``;
- ``unscoped``: everything else (other programs of the window, ops the
  compiler made with no name, ops of no module whose text is known).

A reader that needs one scope rather than a phase takes ``scope_ms`` (the
self time of the ops with that scope anywhere in their ``op_name``,
forward and backward apart) or ``scope_ops`` (those ops themselves).  All
three read one naming of the window's ops, made once per run.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence

from harness import trace as tr

PHASES = ("vocab", "local_fwd", "local_bwd", "optimizer", "delta",
          "compress", "aggregate", "unscoped")
SCOPE = re.compile(r"\b(?:hcef|lm)\.[a-z_]+")
PHASE_OF_SCOPE = {"lm.embed": "vocab", "lm.head": "vocab",
                  "hcef.sgd": "optimizer", "hcef.grad_stats": "optimizer",
                  "hcef.delta": "delta", "hcef.compress": "compress",
                  "hcef.aggregate": "aggregate", "hcef.gossip": "aggregate"}
REMAT = "rematted_computation"
# a module execution is read with the HLO text that names at least this
# share of its ops by instruction name and result type
MIN_MATCH = 0.99

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = "
                    r"(\(*[a-z0-9]+\[[0-9,]*\]).*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"[^%)]*%([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)[ (]")
_EVENT = re.compile(r"^%?([^ ]+) = (\(*[a-z0-9]+\[[0-9,]*\])")


def phase_of(op_name: str) -> str:
    """The phase of an op by the innermost program scope in its name."""
    scopes = SCOPE.findall(op_name)
    if not scopes:
        return "unscoped"
    inner = scopes[-1]
    if inner in PHASE_OF_SCOPE:
        return PHASE_OF_SCOPE[inner]
    return "local_bwd" if "transpose(" in op_name else "local_fwd"


def hlo_op_names(hlo_text: str) -> Dict[str, tuple]:
    """{instruction name: (result type, op_name, opcode)} of the
    instructions of an HLO module's text that run as ops (those of a
    computation another one ``calls``, a fusion's or an async op's, do
    not).  An instruction the compiler made without an ``op_name`` takes
    one from what it works on: the scoped ``op_name`` nearest the root of
    the computation it calls (a fusion's, an async op's), or else its
    first operand's (a copy's)."""
    instrs, comps, cur = {}, {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            cur = m.group(1) if m and line.rstrip().endswith("{") else None
            comps.setdefault(cur, [])
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        on = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        first = _OPERAND.match(line, m.end())
        instrs[m.group(1)] = (m.group(2), on.group(1) if on else "",
                              m.group(3), calls.group(1) if calls else None,
                              first.group(1) if first else None)
        comps[cur].append(m.group(1))
    memo = {}

    def op_name(name, depth=0):
        if name not in instrs or depth > 16:
            return ""
        if name not in memo:
            _, on, _, calls, first = instrs[name]
            if not on and calls is not None:
                fused = [instrs[n][1] for n in reversed(comps.get(calls, []))]
                on = next((o for o in fused if SCOPE.search(o)), "")
            memo[name] = on or op_name(first, depth + 1)
        return memo[name]

    called = {v[3] for v in instrs.values() if v[3] is not None}
    return {n: (instrs[n][0], op_name(n), instrs[n][2])
            for c, names in comps.items() if c not in called for n in names}


def event_key(name: str):
    """(instruction name, result type) of a device-plane op event."""
    m = _EVENT.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


def _best_map(ops: Sequence[tr.Event], maps: Sequence[dict]):
    """The op-name map that names most of ``ops`` by instruction name and
    result type; None unless it names at least ``MIN_MATCH`` of them."""
    keys = [event_key(e.name) for e in ops]
    best, best_n = None, 0
    for m in maps:
        n = sum(1 for k, t in keys if k in m and m[k][0] == t)
        if n > best_n:
            best, best_n = m, n
    if not keys or best_n < MIN_MATCH * len(keys):
        return None
    return best


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is one of the names of ``op_name``'s path (a
    path component, or the argument of a transform such as
    ``transpose(jvp(lm.head))``)."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?=$|[/)])",
                     op_name) is not None


@dataclass
class NamedOps:
    """The ops of one chip in a window, each with its self time there."""
    ops: List[tuple]  # (event, self ns, op_name), op_name "" if unknown
    modules_named: int  # module executions read with a program's text
    modules: int


def named_ops(events: Iterable[tr.Event], plane: str, t0: float, t1: float,
              maps: Sequence[dict]) -> NamedOps:
    """Each op with self time in [t0, t1] on ``plane``, named by the map
    (one ``hlo_op_names`` map per program the window may have run) that
    names its module execution; an op no map names has the name ""."""
    events = list(events)
    ops = sorted(tr.select(events, plane=plane, line=tr.OPS_LINE),
                 key=lambda e: e.start_ns)
    starts = [e.start_ns for e in ops]
    mods = [m for m in tr.select(events, plane=plane, line=tr.MODULES_LINE)
            if m.end_ns > t0 and m.start_ns < t1]
    name_of = {}
    named = 0
    for mod in mods:
        lo = bisect.bisect_left(starts, mod.start_ns)
        hi = bisect.bisect_right(starts, mod.end_ns)
        inside = [e for e in ops[lo:hi] if e.end_ns <= mod.end_ns]
        m = _best_map(inside, maps)
        if m is None:
            continue
        named += 1
        for e in inside:
            k, _ = event_key(e.name)
            if k in m:
                name_of[id(e)] = m[k][1]
    return NamedOps([(e, own, name_of.get(id(e), ""))
                     for e, own in tr.self_times(ops, t0, t1) if own > 0],
                    named, len(mods))


@dataclass
class Phases:
    """Device self time of a window by phase, in ns."""
    ns: Dict[str, float]
    remat_ns: float  # the part of ``local_bwd`` that re-runs the forward
    modules_named: int  # module executions read with a program's text
    modules: int
    top: Dict[str, Counter] = field(repr=False)  # phase -> op -> ns


def phases(named: NamedOps) -> Phases:
    """Each named op's self time given to its phase."""
    out = Phases(dict.fromkeys(PHASES, 0.0), 0.0, named.modules_named,
                 named.modules, {p: Counter() for p in PHASES})
    for e, own, on in named.ops:
        ph = phase_of(on)
        out.ns[ph] += own
        out.top[ph][tr.short_name(e.name)] += own
        if ph == "local_bwd" and REMAT in on:
            out.remat_ns += own
    return out


def phase_ns(events: Iterable[tr.Event], plane: str, t0: float, t1: float,
             maps: Sequence[dict]) -> Phases:
    """Each op's self time in [t0, t1] on ``plane`` given to its phase.
    ``maps``: one ``hlo_op_names`` map per program the window may have
    run; an op of a module execution no map names is ``unscoped``."""
    return phases(named_ops(events, plane, t0, t1, maps))


def fl_round_maps(ctx) -> List[dict]:
    """``hlo_op_names`` of an fl cell's intra and gossip round programs,
    built as ``kinds/fl.py`` builds them (``make_round_step`` compiled by
    ``launch.train.compile_step`` for the same argument shapes), so the
    compile cache gives back the programs the window ran."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import FLTopology
    from repro.core.round import abstract_state, make_round_step
    from repro.launch.train import compile_step

    from harness import spec
    from harness.program import model_config
    fl = spec.load_kind("fl")
    t, c = ctx.traffic, ctx.config
    cfg = model_config(c)
    hcef = fl.hcef_config(get_config(c["repo_config"]).hcef, t)
    topo = FLTopology(clusters=t["clusters"],
                      devices_per_cluster=t["devices_per_cluster"])
    R = topo.num_devices
    vec = jax.ShapeDtypeStruct((R,), jnp.float32)
    args = (abstract_state(cfg, hcef, topo),
            {"tokens": jax.ShapeDtypeStruct(
                (R * t["tau"] * t["seqs_per_step"], t["seq_len"] + 1),
                jnp.int32)},
            vec, vec, jax.ShapeDtypeStruct((R, 2), jnp.uint32))
    return [hlo_op_names(compile_step(
        make_round_step(cfg, hcef, topo, gossip=g), args)[0].as_text())
        for g in (False, True)]


def window_ops(ctx) -> Optional[NamedOps]:
    """The named ops of an fl cell's traced window on chip 0, or None
    without one.  Made once per run and kept on ``ctx``, so the round
    programs are compiled for their names once, whatever reads them."""
    if "_window_ops" not in vars(ctx):
        ctx._window_ops = None
        v = ctx.trace
        if ctx.kind == "fl" and v is not None and v.planes and ctx.rounds:
            ctx._window_ops = named_ops(v.events, v.planes[0], v.t0, v.t1,
                                        fl_round_maps(ctx))
    return ctx._window_ops


def phase_ms(ctx) -> Optional[Dict[str, float]]:
    """{phase: device ms per round of the traced window} of an fl cell on
    chip 0, or None where no op of the window carries a program scope (a
    program without scopes).  Computed once per run; the first call prints
    the phase table, each phase's share of busy time and its top ops to
    stderr."""
    if "_phase_ms" in vars(ctx):
        return ctx._phase_ms
    ctx._phase_ms = None
    named = window_ops(ctx)
    if named is None:
        return None
    ph = phases(named)
    if ph.ns["unscoped"] == sum(ph.ns.values()):
        return None
    v = ctx.trace
    ops = tr.select(v.events, plane=v.planes[0], line=tr.OPS_LINE)
    busy = tr.busy_ns(ops, v.t0, v.t1)
    per = 1e-6 / ctx.rounds
    ctx._phase_ms = {k: x * per for k, x in ph.ns.items()}
    note = lambda k, v: print(f"bench: {k}: {v}", file=sys.stderr)
    note("phase_ms", _fmt(ctx._phase_ms))
    share = _fmt({k: 100 * x / busy for k, x in ph.ns.items()})
    note("phase_share_of_busy", f"{share} (%; the phases sum to "
         f"{100 * sum(ph.ns.values()) / busy:.3f}% of busy)")
    note("local_bwd_remat_ms", f"{ph.remat_ns * per:.4g}")
    note("module_executions_named", f"{ph.modules_named} of {ph.modules}")
    note("phase_top_ops_ms", {k: [[n, float(f"{x * per:.4g}")]
                                  for n, x in c.most_common(4)]
                              for k, c in ph.top.items()})
    return ctx._phase_ms


def scope_ops(ctx, scope: str) -> Optional[List[tuple]]:
    """(event, self ns, op_name) of each op of the traced window on chip 0
    with ``scope`` in its ``op_name`` (``in_scope``), or None without a
    traced window."""
    named = window_ops(ctx)
    if named is None:
        return None
    return [op for op in named.ops if in_scope(op[2], scope)]


def scope_ms(ctx, scope: str) -> Optional[Dict[str, float]]:
    """{"fwd": ms, "bwd": ms}: device self time per round of the traced
    window on chip 0 of the ops under ``scope``, outside and inside an
    autodiff ``transpose(...)`` (the backward, with any remat re-run of
    the forward); None where no op carries the scope."""
    ops = scope_ops(ctx, scope)
    if not ops:
        return None
    ns = {"fwd": 0.0, "bwd": 0.0}
    for _, own, on in ops:
        ns["bwd" if "transpose(" in on else "fwd"] += own
    per = 1e-6 / ctx.rounds
    return {k: x * per for k, x in ns.items()}


def _fmt(d):
    return {k: float(f"{x:.4g}") for k, x in d.items()}
