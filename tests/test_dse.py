"""Design-space-exploration service tests.

The two load-bearing claims get the heavy machinery:

* **kill → resume → zero recomputation**: a real ``python -m repro.dse run``
  subprocess is SIGKILLed mid-sweep; resuming against the same store
  computes only what the kill lost (accounting proves it: a final pass
  computes 0), and the frontier file is byte-identical to one from an
  uninterrupted run in a separate store.
* **Pareto correctness**: the frontier equals the brute-force non-dominated
  subset under randomized (fifo%, slots, cost) triples — seeded-random
  always, hypothesis-driven where hypothesis is installed.

Everything else: spec round-trips and deterministic expansion, the
content-addressed store, the execution-manager failure contract, the sweep
engine's per-job error records, lowering-override provenance and cost
effect, parametric/concrete metric parity, and the roofline loader's
corrupt-record warnings.
"""
from __future__ import annotations

import json
import os
import pathlib
import random
import signal
import subprocess
import sys
import tempfile
import time

import pytest

from repro.core.sweep import SweepJob, run_job, sweep_parallel
from repro.core.tiling import rescale_tilings
from repro.dse import (ArtifactStore, DSEService, Experiment, SpecError,
                       default_experiment, make_manager, pareto_front,
                       run_group)
from repro.dse.pareto import dominates, objective_vector

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def tiny_experiment(**kw):
    kw.setdefault("kernels", ["gemm", "atax"])
    kw.setdefault("tile_sizes", [2, 4])
    kw.setdefault("topologies", ["sequential"])
    kw.setdefault("size_count", 2)
    return default_experiment("tiny", **kw)


# ------------------------------------------------------------------- specs --

def test_spec_round_trip_and_stable_ids():
    exp = tiny_experiment()
    doc = json.loads(json.dumps(exp.as_dict()))
    again = Experiment.from_dict(doc)
    assert again.as_dict() == exp.as_dict()
    assert again.experiment_id == exp.experiment_id
    # expansion is deterministic: same points, same keys, same order
    keys = [p.key for p in exp.points()]
    assert keys == [p.key for p in again.points()]
    assert len(set(keys)) == len(keys)


def test_spec_validation_names_the_field():
    exp = tiny_experiment()
    exp.topologies = ["ring"]
    with pytest.raises(SpecError, match="topology"):
        exp.groups()
    exp = tiny_experiment()
    exp.lowering_overrides = [{"*": "carrier-pigeon"}]
    with pytest.raises(SpecError, match="lowering"):
        exp.groups()
    exp = tiny_experiment()
    exp.sizes = {"kind": "fibonacci"}
    with pytest.raises(SpecError, match="sizes.kind"):
        exp.groups()
    # specs written while the spec had a measured axis carry a null one
    doc = dict(tiny_experiment().as_dict(), measure=None)
    assert Experiment.from_dict(doc).as_dict() == tiny_experiment().as_dict()
    doc["measure"] = {"kernels": ["jacobi-1d"]}
    with pytest.raises(SpecError, match="measure"):
        Experiment.from_dict(doc)


def test_point_key_ignores_axis_labels():
    exp = tiny_experiment()
    p = exp.points()[0]
    relabeled = type(p)(p.kernel, "renamed-tiling", p.tiling, p.topology,
                        p.sizes, p.overrides, "renamed-ov", p.pow2)
    assert relabeled.key == p.key


def test_size_axis_explicit_env_override():
    exp = tiny_experiment()
    exp.sizes = dict(exp.sizes, envs={"gemm": [{"N": 20}]})
    envs = {g.kernel: g.size_envs for g in exp.groups()}
    assert envs["gemm"] == ({"N": 20},)
    assert len(envs["atax"]) == 2          # lattice axis untouched


# ------------------------------------------------------------------- store --

def test_store_points_and_corrupt_record(tmp_path):
    store = ArtifactStore(str(tmp_path))
    exp = tiny_experiment()
    eid = store.init_experiment(exp)
    assert store.load_experiment(eid).as_dict() == exp.as_dict()
    store.put_point(eid, "k1", {"kernel": "gemm", "metrics": {}})
    assert store.has_point(eid, "k1")
    assert store.get_point(eid, "k1")["kernel"] == "gemm"
    (store.points_dir(eid) / "k2.json").write_text("{not json")
    assert store.get_point(eid, "k2") is None
    assert store.stats["misses"] == 1
    assert [p["kernel"] for p in store.iter_points(eid)] == ["gemm"]


def test_store_env_root(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_DSE_STORE", str(tmp_path / "fromenv"))
    assert str(ArtifactStore().root) == str(tmp_path / "fromenv")


# ------------------------------------------------------------------ pareto --

def _brute_non_dominated(vecs):
    return {i for i, a in enumerate(vecs)
            if not any(b != a and dominates(b, a) for b in vecs)}


def _pareto_matches_bruteforce(triples):
    pts = [{"key": f"p{i}",
            "metrics": {"fifo_fraction": f, "total_slots": s,
                        "predicted_s": c}}
           for i, (f, s, c) in enumerate(triples)]
    front = pareto_front(pts)
    got = {e["key"] for e in front["frontier"]}
    vecs = [objective_vector(p) for p in pts]
    want = set()
    for i in sorted(_brute_non_dominated(vecs)):
        # duplicates of a frontier vector are all non-dominated; keep them
        want.add(f"p{i}")
    assert got == want
    # every dominated point names a dominating frontier-or-better point
    by_key = {f"p{i}": v for i, v in enumerate(vecs)}
    for e in front["dominated"]:
        assert dominates(by_key[e["dominated_by"]], by_key[e["key"]])


def test_pareto_random_triples_seeded():
    rng = random.Random(7)
    for _ in range(50):
        n = rng.randrange(1, 25)
        triples = [(rng.choice([0.0, 0.25, 0.5, 1.0]),
                    rng.randrange(1, 200),
                    rng.choice([1e-9, 2e-9, 5e-9])) for _ in range(n)]
        _pareto_matches_bruteforce(triples)


def test_pareto_error_points_are_skipped():
    pts = [{"key": "ok", "metrics": {"fifo_fraction": 1.0,
                                     "total_slots": 1, "predicted_s": 1.0}},
           {"key": "err", "error": {"type": "X", "message": "boom"},
            "metrics": {"fifo_fraction": 1.0, "total_slots": 0,
                        "predicted_s": 0.0}}]
    front = pareto_front(pts)
    assert front["skipped"] == 1
    assert [e["key"] for e in front["frontier"]] == ["ok"]


try:
    from hypothesis import given, settings, strategies as st

    @given(st.lists(st.tuples(
        st.floats(min_value=0, max_value=1, allow_nan=False),
        st.integers(min_value=0, max_value=10**6),
        st.floats(min_value=0, max_value=1, allow_nan=False)),
        min_size=1, max_size=40))
    @settings(max_examples=200, deadline=None)
    def test_pareto_property_hypothesis(triples):
        _pareto_matches_bruteforce(triples)
except ImportError:                                      # pragma: no cover
    @pytest.mark.skip(reason="property tests need hypothesis "
                             "(not installed in this environment)")
    def test_pareto_property_hypothesis():
        pass


# ------------------------------------------------------------ worker/units --

@pytest.fixture(scope="module")
def tiny_store():
    with tempfile.TemporaryDirectory() as d:
        exp = tiny_experiment()
        svc = DSEService(exp, ArtifactStore(d), manager="inline")
        summary = svc.run()
        yield exp, svc, summary


def test_inline_end_to_end(tiny_store):
    exp, svc, summary = tiny_store
    assert summary["errors"] == 0
    assert summary["computed"] == summary["points_total"] == 8
    pts = list(svc.store.iter_points(exp.experiment_id))
    assert len(pts) == 8
    for p in pts:
        m = p["metrics"]
        assert 0.0 <= m["fifo_fraction"] <= 1.0
        assert m["total_slots"] > 0 and m["predicted_s"] > 0
        assert p["provenance"]["size_mode"] in ("parametric", "concrete",
                                                "concrete-fallback")
    # gemm groups ran parametric (sizes on the proved lattice)
    assert any(p["provenance"]["size_mode"] == "parametric" for p in pts
               if p["kernel"] == "gemm")


def test_rerun_computes_nothing(tiny_store):
    exp, svc, _ = tiny_store
    again = svc.run()
    assert again["computed"] == 0 and again["submitted"] == 0
    assert again["from_store"] == again["points_total"]


def test_parametric_concrete_metric_parity(tiny_store):
    """PR 9's byte parity, surfaced at the DSE layer: forcing the size axis
    concrete changes provenance but neither reports nor metrics."""
    exp, svc, _ = tiny_store
    forced = tiny_experiment()
    forced.size_mode = {"default": "concrete"}
    with tempfile.TemporaryDirectory() as d:
        svc2 = DSEService(forced, ArtifactStore(d), manager="inline")
        assert svc2.run()["errors"] == 0
        a = {p["key"]: p for p in svc.store.iter_points(exp.experiment_id)}
        b = {p["key"]: p
             for p in svc2.store.iter_points(forced.experiment_id)}
        assert set(a) == set(b)            # size_mode is not identity
        for k in a:
            assert a[k]["report"] == b[k]["report"]
            assert a[k]["metrics"] == b[k]["metrics"]
            assert {a[k]["provenance"]["size_mode"],
                    b[k]["provenance"]["size_mode"]} <= {
                        "parametric", "concrete"}


def test_lowering_override_cost_and_provenance(tiny_store):
    exp, svc, _ = tiny_store
    forced = tiny_experiment()
    forced.lowering_overrides = [None, {"*": "reorder-buffer"}]
    with tempfile.TemporaryDirectory() as d:
        svc2 = DSEService(forced, ArtifactStore(d), manager="inline")
        assert svc2.run()["errors"] == 0
        pts = list(svc2.store.iter_points(forced.experiment_id))
        planned = {(p["kernel"], p["tiling_id"], json.dumps(p["sizes"])): p
                   for p in pts if p["override_id"] == "planned"}
        for p in pts:
            if p["override_id"] == "planned":
                continue
            base = planned[(p["kernel"], p["tiling_id"],
                            json.dumps(p["sizes"]))]
            assert p["provenance"]["overrides_applied"], \
                "override must be recorded in provenance"
            for plan in p["report"]["plans"]:
                assert plan["lowering"] == "reorder-buffer"
            # everything on the reorder buffer costs more than the plan
            assert p["metrics"]["predicted_s"] \
                > base["metrics"]["predicted_s"]


def test_worker_bad_kernel_yields_error_points():
    exp = tiny_experiment()
    task = exp.groups()[0].as_dict()
    task["kernel"] = "no-such-kernel"
    results = run_group(task)
    assert len(results) == 2
    assert all(r["error"]["type"] == "KeyError" for r in results)


def test_manager_registry():
    with pytest.raises(ValueError, match="unknown execution manager"):
        make_manager("carrier-pigeon")
    slurm = make_manager("slurm")
    slurm.submit("t", tiny_experiment().groups()[0].as_dict())
    (task_id, results), = list(slurm.drain())
    assert all(r["error"] for r in results)           # stub refuses politely
    assert "sbatch" in results[0]["error"]["message"]


# ------------------------------------------------- sweep failure contract --

def test_run_job_contains_per_config_failures(monkeypatch):
    from repro.core.polybench import get
    case = get("gemm")
    good = dict(case.tilings)
    jobs_cfgs = (good, good, good)
    # `repro.core.sweep` the attribute is the sweep() function (core's
    # __init__ re-export wins); reach the module through sys.modules
    sweep_mod = sys.modules["repro.core.sweep"]
    real = sweep_mod._run_stages
    calls = {"n": 0}

    def flaky(a, stages, pow2, topology):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("synthetic mid-sweep failure")
        return real(a, stages, pow2, topology)

    monkeypatch.setattr(sweep_mod, "_run_stages", flaky)
    out = run_job(SweepJob(kernel="gemm", tilings=jobs_cfgs))
    assert len(out) == 3
    assert "error" not in out[0] and "error" not in out[2]
    err = out[1]["error"]
    assert err == {"kernel": "gemm", "config_index": 1,
                   "type": "RuntimeError",
                   "message": "synthetic mid-sweep failure"}


def test_run_job_unknown_kernel_fills_all_slots():
    out = run_job(SweepJob(kernel="not-a-kernel", tilings=({}, {})))
    assert [r["error"]["config_index"] for r in out] == [0, 1]
    assert all(r["error"]["type"] == "KeyError" for r in out)


def test_sweep_parallel_survives_bad_job():
    from repro.core.polybench import get
    good = SweepJob(kernel="atax", tilings=(dict(get("atax").tilings),))
    bad = SweepJob(kernel="not-a-kernel", tilings=({},))
    out = sweep_parallel([good, bad], max_workers=2)
    assert "error" not in out[0][0] and out[0][0]["channels"]
    assert out[1][0]["error"]["kernel"] == "not-a-kernel"


# ------------------------------------------------------- kill and resume ---

def _cli(args, env):
    return subprocess.run([sys.executable, "-m", "repro.dse"] + list(args),
                          env=env, capture_output=True, text=True,
                          timeout=240)


@pytest.mark.slow
def test_kill_mid_sweep_resume_zero_recompute(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    flags = ["--default", "--kernels", "gemm,atax,jacobi-1d",
             "--tile-sizes", "2,4", "--size-count", "2"]
    ref_store, kill_store = str(tmp_path / "ref"), str(tmp_path / "kill")

    # reference: uninterrupted run in its own store
    r = _cli(["run", "--store", ref_store] + flags, env)
    assert r.returncode == 0, r.stderr

    # victim: kill the process once the store holds a few points
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.dse", "run", "--store", kill_store]
        + flags, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    exp = default_experiment("polybench-full",
                             kernels=["gemm", "atax", "jacobi-1d"],
                             tile_sizes=[2, 4], size_count=2)
    store = ArtifactStore(kill_store)
    deadline = time.time() + 120
    while time.time() < deadline:
        done = len(store.point_keys(exp.experiment_id))
        if 0 < done < len(exp.points()):
            break
        if proc.poll() is not None:        # finished before we could kill it
            pytest.skip("run completed before the kill window")
        time.sleep(0.02)
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    survived = len(store.point_keys(exp.experiment_id))
    assert 0 < survived < len(exp.points()), "kill missed the window"

    # resume: only the lost points are computed (cache-hit accounting)
    svc = DSEService(exp, store, manager="inline")
    summary = svc.run()
    assert summary["from_store"] >= survived
    assert summary["computed"] == summary["points_total"] \
        - summary["from_store"]
    # zero-recompute pass
    final = svc.run()
    assert final["computed"] == 0 and final["submitted"] == 0
    assert final["from_store"] == final["points_total"]

    # frontier byte-identical to the uninterrupted reference
    svc.frontier()
    ref = (pathlib.Path(ref_store) / "experiments" / exp.experiment_id
           / "frontier.json").read_bytes()
    got = (pathlib.Path(kill_store) / "experiments" / exp.experiment_id
           / "frontier.json").read_bytes()
    assert got == ref


def test_cli_worker_round_trip(tmp_path):
    from repro.dse.__main__ import main
    task = tiny_experiment().groups()[0]
    task_f, out_f = tmp_path / "task.json", tmp_path / "out.json"
    task_f.write_text(json.dumps(task.as_dict()))
    assert main(["worker", "--task", str(task_f), "--out", str(out_f)]) == 0
    results = json.loads(out_f.read_text())
    assert len(results) == len(task.size_envs)
    assert all("metrics" in r for r in results)


# ----------------------------------------------------- roofline satellite --

def test_roofline_load_warns_on_corrupt_record(tmp_path):
    from repro.launch.roofline import load
    (tmp_path / "good.json").write_text(json.dumps({"mesh": "16x16"}))
    (tmp_path / "bad.json").write_text("{truncated")
    with pytest.warns(UserWarning, match="bad.json"):
        recs, skipped = load(tmp_path)
    assert len(recs) == 1
    assert skipped == [str(tmp_path / "bad.json")]


def test_predict_report_cost_prices_reorder_buffer(tiny_store):
    from repro.launch.roofline import predict_report_cost
    exp, svc, _ = tiny_store
    doc = next(iter(svc.store.iter_points(exp.experiment_id)))["report"]
    base = predict_report_cost(doc)
    assert base["predicted_s"] > 0
    forced = json.loads(json.dumps(doc))
    for plan in forced["plans"]:
        plan["lowering"] = "reorder-buffer"
    worse = predict_report_cost(forced)
    assert worse["hbm_bytes"] > base["hbm_bytes"]
    assert worse["predicted_s"] >= base["predicted_s"]


def test_peek_polyhedron_cache(tmp_path):
    from repro.core import (peek_polyhedron_cache, save_polyhedron_cache)
    path = str(tmp_path / "verdicts.pkl")
    save_polyhedron_cache(path)
    info = peek_polyhedron_cache(path)
    assert info and info["version"].startswith("repro-polyhedron-cache")
    bad = tmp_path / "junk.pkl"
    bad.write_bytes(b"\x80\x04junk")
    assert peek_polyhedron_cache(str(bad)) is None
    assert peek_polyhedron_cache(str(tmp_path / "missing.pkl")) is None
