"""The harness end to end on the CPU at the program's test size: every
name in BENCHMARK.json resolves, a tiny fl cell runs through the same
driver as on the chip and reads correct, each fault planted under the
timed path and the control read not correct, and a run without a TPU or
without the program's sources exits non-zero with no result."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness import compare, spec

BENCH = spec.BENCH_DIR
ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_workload_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert (BENCH / "kinds" / f"{cell.traffic['kind']}.py").is_file()
        if cell.traffic["kind"] == "fl":
            assert cell.limits["numbers"] and set(cell.limits["numbers"]) \
                <= {"loss_gap", "grad_gap", "update_gap", "grad_rel_err"}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_reader(m.name))
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_benchmark_json_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert len(c["why"]) <= 200
        cfg = spec.load_json(ROOT / c["file"])
        assert cfg["source"] == c["source"]
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size")), k
    e2e = {m["name"] for m in bench["end_to_end"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    for m in bench["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.fixture(scope="module")
def thmin_limits():
    return spec.load_json(spec.limits_path("qwen0.5b-fl-thmin"))["numbers"]


def _cpu_run(cell, fault=None):
    import jax
    import run
    return run.execute(cell, 2**31 + 7, 0.5, False, jax.devices("cpu")[:1],
                       fault=fault, t_start=0.0)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_fl_rehearsal_and_faults(tiny_fl_cell, thmin_limits, fault):
    out = _cpu_run(tiny_fl_cell(thmin_limits), fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-3] == "checks"  # checks last in the result line
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["_compiles_in_window"] == 0


@pytest.mark.parametrize("workload", ["qwen0.5b-fl-thmin",
                                      "qwen0.5b-fl-dense"])
def test_fl_control_is_not_correct(tiny_fl_cell, workload):
    """The reference in fp8 put in the program's place fails one of the
    cell's limits, and the program passes them."""
    from harness import spec as s
    kind = s.load_kind("fl")
    real = s.resolve(workload)
    limits = real.limits["numbers"]
    cell = tiny_fl_cell(limits, controller=real.traffic["controller"],
                        expect_theta=real.traffic["expect_theta"])
    fed = kind.Federation(cell, 5)
    n = cell.traffic["check_rounds"]
    prog = kind.program_readings(fed, n)
    fed.free()
    ref = kind.reference_readings(fed, n, prog["theta"], prog["gossip"])
    ctl = kind.reference_readings(fed, n, prog["theta"], prog["gossip"],
                                  lowp="fp8")
    ok, rows = compare.judge(kind.check_numbers(ctl, ref), limits)
    assert not ok, rows
    ok, rows = compare.judge(kind.check_numbers(prog, ref), limits)
    assert ok, rows


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen0.5b-fl-thmin",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_prints_no_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


TINY_SERVE = {"kind": "serve", "slots": 4, "page_size": 16, "kv_dtype": None,
              "prompt": {"median": 20, "sigma": 0.8, "min": 8, "max": 48},
              "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 12},
              "rate_per_s": 8.0, "check_requests": 4, "trace_seconds": 2}


def test_serve_rehearsal_reaches_the_measurement():
    """The serving driver at the program's test size: the schedule offers
    the same sizes for every seed, ``Engine.serve`` runs it with no compile
    in the window, the serving readers read, and the served tokens are
    compared with the reference.  (``correct`` is not asserted: the cell is
    out of BENCHMARK.json while ``Engine.serve`` starts decoding one
    position late; PERF.md, section 7.)"""
    import jax
    import run
    from conftest import TINY_QWEN, make_cell
    from harness import spec as s
    kind = s.load_kind("serve")
    a = kind.schedule(TINY_SERVE, 257, 1, 2.0)
    b = kind.schedule(TINY_SERVE, 257, 2**31 + 9, 2.0)
    assert sorted(len(p) for _, p, _, _ in a) == \
        sorted(len(p) for _, p, _, _ in b)
    assert max(len(p) for _, p, _, _ in a) == 48
    cell = make_cell(dict(TINY_QWEN, initializer_range=0.5), TINY_SERVE,
                     {"served_logit_gap": 1e-3})
    cell.end_to_end = [s.Metric(n, "ms", "lower", "host_clock")
                       for n in ("ttft_p90_ms", "tpot_p90_ms", "setup_s")]
    out = run.execute(cell, 3, 2.0, False, jax.devices("cpu")[:1],
                      t_start=0.0)
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert out["_compiles_in_window"] == 0
    gap = out["checks"]["served_logit_gap"]["value"]
    assert gap == gap and gap >= 0.0


def test_serve_witness_static_path_agrees_with_the_reference():
    from conftest import TINY_QWEN, make_cell
    from harness import spec as s
    kind = s.load_kind("serve")
    cell = make_cell(dict(TINY_QWEN, initializer_range=0.5), TINY_SERVE, {})
    w = kind.witness(cell, 5, 2.0, static_requests=2)
    assert w["static_path_gap_max"] == pytest.approx(0.0, abs=1e-4)
    assert w["first_token_gap_max"] == pytest.approx(0.0, abs=1e-4)
