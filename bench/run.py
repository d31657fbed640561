#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix, limits and metric readers are found
by the names in ``BENCHMARK.json`` (``bench/configs``, ``bench/traffic``,
``bench/limits``, ``bench/metrics``); the driver for the traffic's
``kind`` is ``bench/kinds/<kind>.py``.  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``: every number compared for ``correct``
beside its limit.  The same numbers are the last lines of standard error.
Without a TPU, with fewer chips than the cell asks for, or without the
program's sources beside ``bench/``, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def compile_counter():
    """() -> count of compiles and compile-cache reads since the call."""
    import jax
    n = [0]

    def listen(event, duration, **kw):
        if event in COMPILE_EVENTS:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)

    def count():
        jax.monitoring.unregister_event_duration_listener(listen)
        return n[0]

    return count


@contextlib.contextmanager
def profiler(trace_dir: str):
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def setup_jax():
    """Compile cache at a fixed path inside the checkout (the program's
    ``enable_compile_cache`` takes it from the environment), every program
    cached however fast it compiled."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def trace_view(events, chips: int):
    """The traced window and the device planes of the chips used."""
    from harness import trace as tr
    win = tr.select(events, plane=tr.HOST_PLANE, name=r"^bench\.window$")
    if not win:
        raise RuntimeError("no bench.window span in the trace")
    t0, t1 = win[0].start_ns, win[0].end_ns
    planes = tr.device_planes(events)[:chips]
    return SimpleNamespace(events=events, t0=t0, t1=t1, planes=planes,
                           host=tr.select(events, plane=tr.HOST_PLANE,
                                          name=r"^bench\."))


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            fault=None, t_start: float = None) -> dict:
    """Drive one run of ``cell`` and read its metrics; returns the result
    object (``checks`` last).  ``devices``: the chips to use (the tests
    pass the CPU here, with ``fault`` breaking the timed path)."""
    import jax
    from harness import trace as tr
    from harness.device import peaks_for
    from harness import counts

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    env = SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        t_start=T_START if t_start is None else t_start, devices=devices,
        fault=fault, compile_counter=compile_counter,
        tracer=(lambda: profiler(trace_dir)))
    kind = spec.load_kind(cell.traffic["kind"])
    try:
        res = kind.run(env)
        ctx = res["ctx"]
        ctx.peaks = peaks_for(devices[0].device_kind) \
            if devices[0].platform == "tpu" else None
        ctx.counts = counts
        ctx.trace = None
        if trace:
            ctx.trace = trace_view(tr.load_xplane(trace_dir), cell.chips)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.load_reader(m.name)(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and ctx.trace.planes:
        v = ctx.trace
        busy = []
        for plane in v.planes:
            ops = tr.select(v.events, plane=plane, line=tr.OPS_LINE)
            busy.append(tr.busy_ns(ops, v.t0, v.t1))
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (v.t1 - v.t0) * 1e-9
        ops0 = tr.select(v.events, plane=v.planes[0], line=tr.OPS_LINE)
        out["breakdown"] = {
            "device_ops": tr.op_totals(ops0, v.t0, v.t1),
            "idle_gaps": tr.attribute_gaps(tr.gaps(ops0, v.t0, v.t1),
                                           v.host)}
    out["checks"] = {n: {"value": val, "limit": lim}
                     for n, val, lim in res["checks"]}
    out["_compiles_in_window"] = res["compiles_in_window"]
    out["_notes"] = res.get("notes", {})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program sources at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    try:
        cell = spec.resolve(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    jax = setup_jax()
    from harness.device import DeviceError, require_chips
    try:
        require_chips(jax.devices(), cell.chips)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    devices = jax.devices()[:cell.chips]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    out = execute(cell, args.seed, args.seconds, bool(args.trace), devices)
    n_comp = out.pop("_compiles_in_window")
    for k, v in out.pop("_notes").items():
        print(f"bench: {k}: {v}", file=sys.stderr)
    print(f"bench: {args.workload} seed={args.seed} device "
          f"{out['device']['kind']} x{out['device']['count']}; compiles "
          f"in the window: {n_comp}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out, allow_nan=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
