"""Parametric (symbolic-size) analysis: prove once, evaluate per size.

The load-bearing contract is byte parity: for every PolyBench kernel the
template built by ONE symbolic analysis must instantiate, at every concrete
size on its proved lattice, to exactly the report a from-scratch concrete
``analyze(...)`` produces (modulo the diagnostics-only ``cache`` field).
Everything else — proof statuses, closed forms, fallbacks — is checked on
top of that.
"""
import json
import warnings
from fractions import Fraction

import pytest

from repro.core import (ParametricAnalysis, ParametricFallbackWarning,
                        SizePoly, analyze, report_payload, sweep, symbolic)
from repro.core.parametric import _degree_bounds, _strides
from repro.core.polybench import get, jacobi_1d_paper, kernel_names
from repro.core.tiling import Tiling, rescale_tilings


def _concrete_payload(case, env, stages=("classify", "fifoize", "size",
                                         "plan"), tilings=None):
    a = analyze(case.kernel, params=dict(env),
                tilings=case.tilings if tilings is None else tilings)
    for s in stages:
        a = getattr(a, s)()
    return report_payload(a.report())


def _dumps(doc):
    return json.dumps(doc, sort_keys=True)


# ------------------------------------------------------------ SizePoly unit

def test_sizepoly_eval_and_str():
    p = SizePoly(("N", "T"), {(2, 0): Fraction(3), (1, 1): Fraction(1),
                              (0, 0): Fraction(-4)})
    assert p(N=5, T=2) == 3 * 25 + 10 - 4
    assert p.eval_int({"N": 5, "T": 2}) == 81
    assert str(p) == "3*N**2 + N*T - 4"
    assert p.degree() == 2


def test_sizepoly_eval_int_rejects_fractional_values():
    p = SizePoly(("N",), {(1,): Fraction(1, 2)})
    assert p(N=3) == Fraction(3, 2)
    with pytest.raises(ValueError):
        p.eval_int({"N": 3})
    assert p.eval_int({"N": 4}) == 2


def test_sizepoly_add_and_lead_term():
    a = SizePoly(("N",), {(2,): Fraction(1), (0,): Fraction(3)})
    b = SizePoly(("N",), {(2,): Fraction(-1), (1,): Fraction(5)})
    s = a + b
    assert s(N=7) == 5 * 7 + 3
    assert (a + a).lead_term() == "2*N**2"


def test_sizepoly_dict_round_trip():
    p = SizePoly(("N", "T"), {(3, 1): Fraction(7, 2), (0, 0): Fraction(1)})
    q = SizePoly.from_dict(p.as_dict())
    assert q.params == p.params and q.terms == p.terms
    assert str(q) == str(p)


# -------------------------------------------------------------- entry points

def test_analyze_sizes_symbolic_returns_parametric_analysis():
    pa = analyze(get("gemm"), sizes=symbolic)
    assert isinstance(pa, ParametricAnalysis)
    assert pa.symbolic_params == ("N",)


def test_analyze_sizes_mapping_is_concrete_shorthand():
    rep = (analyze(get("gemm"), sizes={"N": 16}).classify().report())
    assert rep.params["N"] == 16 and rep.parametric is None


def test_symbolic_rejects_prebuilt_ppn():
    case = get("gemm")
    ppn = analyze(case).ppn
    with pytest.raises(TypeError):
        analyze(ppn, sizes=symbolic)


def test_symbolic_requires_a_free_parameter():
    with pytest.raises(ValueError):
        analyze(get("gemm"), params={"N": 16}, sizes=symbolic)


def test_validate_stage_needs_concrete_size():
    with pytest.raises(ValueError):
        analyze(get("gemm"), sizes=symbolic).classify().validate()


def test_evaluate_rejects_unknown_parameter():
    pa = analyze(get("gemm"), sizes=symbolic).classify()
    with pytest.raises(ValueError):
        pa.evaluate(M=16)


# ------------------------------------------------------- the parity contract

@pytest.fixture(scope="module")
def gemm_pa():
    pa = (analyze(get("gemm"), sizes=symbolic)
          .classify().fifoize().size().plan())
    with warnings.catch_warnings():
        warnings.simplefilter("error", ParametricFallbackWarning)
        pa.prepare()
    yield pa
    pa.release()


def test_gemm_template_closes_symbolically(gemm_pa):
    assert gemm_pa.status == "symbolic"


def test_gemm_byte_parity_including_extrapolation(gemm_pa):
    case = get("gemm")
    # 48 and 64 are far above the probe window — pure extrapolation
    for n in (16, 24, 48, 64):
        ev = report_payload(gemm_pa.evaluate(N=n))
        assert _dumps(ev) == _dumps(_concrete_payload(case, {"N": n}))


def test_evaluated_report_is_marked_and_carries_no_parametric(gemm_pa):
    rep = gemm_pa.evaluate(N=16)
    assert rep.cache == {"evaluated": True}
    assert rep.parametric is None


def test_off_lattice_size_falls_back_loudly(gemm_pa):
    case = get("gemm")
    with pytest.warns(ParametricFallbackWarning):
        rep = gemm_pa.evaluate(N=17)      # stride lattice is 12 + 4k
    assert _dumps(report_payload(rep)) == _dumps(
        _concrete_payload(case, {"N": 17}))


def test_report_attaches_parametric_doc(gemm_pa):
    rep = gemm_pa.report()
    doc = rep.parametric
    assert doc["status"] == "symbolic"
    assert doc["params"]["N"]["stride"] >= 1
    assert doc["params"]["N"]["threshold"] == 12
    for ch in doc["channels"].values():
        for flag in ("in_order", "unicity"):
            assert ch[flag]["status"] in ("proved", "proved_ray", "probed")
    # symbolic verdicts agree with the evaluated pre-FIFOIZE patterns of
    # the root channels (proofs run on the original network)
    patterns = {c["source"]: c["pattern_before"] for c in rep.channels}
    for name, ch in doc["channels"].items():
        assert ch["pattern"] == patterns[name]


def test_gemm_proves_most_flags(gemm_pa):
    doc = gemm_pa.report().parametric
    s = doc["proof_summary"]
    assert s["proved"] >= 8                 # 9 of 12 close as full proofs
    assert s["proved"] + s["proved_ray"] + s["probed"] == 2 * len(
        doc["channels"])


def test_gemm_closed_forms(gemm_pa):
    forms = gemm_pa.closed_forms()
    # the paper-shaped facts: load channels buffer a full N x N operand,
    # the recovered init->upd FIFO needs exactly one slot
    assert str(forms["load_A->upd.A[1]"]) == "N**2"
    assert forms["load_A->upd.A[1]"](N=40) == 1600
    assert str(forms["init->upd.C[0]"]) == "1"
    doc = gemm_pa.report().parametric
    assert doc["sizes"]["load_A->upd.A[1]"]["lead"] == "N**2"


# Per-kernel parity on the probe window (θ, θ+s, θ+2s): three sizes per
# kernel, every report field byte-identical to concrete analysis.  Probe
# windows start at the registry defaults; kernels in PARITY_RESCALE run a
# finer tiling of the same shape so their probes stay small.
#: heat-3d's reference lattice (strides N=8, T=4) probes N³·T enumerations
#: that take minutes each; benchmarks/bench_parametric.py rescales it too.
PARITY_RESCALE = {"heat-3d": 1}


@pytest.mark.parametrize("name", kernel_names())
def test_all_kernels_three_size_byte_parity(name):
    case = get(name)
    tilings = (rescale_tilings(case.tilings, PARITY_RESCALE[name])
               if name in PARITY_RESCALE else dict(case.tilings))
    pa = (analyze(case, tilings=tilings, sizes=symbolic)
          .classify().fifoize().size().plan())
    with warnings.catch_warnings():
        warnings.simplefilter("error", ParametricFallbackWarning)
        pa.prepare()
    assert pa.status == "symbolic", f"{name} fell back"
    t = pa._template
    for k in (0, 1, 2):
        env = {p: t["theta"][p] + k * t["strides"][p]
               for p in pa.symbolic_params}
        ev = report_payload(pa.evaluate(**env))
        assert _dumps(ev) == _dumps(
            _concrete_payload(case, env, tilings=tilings)), f"{name} at {env}"
    pa.release()


def test_heat3d_reference_and_rescaled_lattices():
    # the reference tiling's 2t-interleaved hyperplanes give T the
    # b // gcd(b, |c|) stride; no parity test probes that lattice
    case = get("heat-3d")
    params = case.kernel.params
    assert _strides(case.kernel, case.tilings, params) == {"N": 8, "T": 4}
    assert _degree_bounds(case.kernel, params) == {"N": 3, "T": 1}
    assert _strides(case.kernel, rescale_tilings(case.tilings, 1),
                    params) == {"N": 2, "T": 1}


def test_paper_kernel_symbolic_at_paper_size():
    case = jacobi_1d_paper()
    pa = analyze(case, sizes=symbolic).classify().fifoize().size().plan()
    with warnings.catch_warnings():
        warnings.simplefilter("error", ParametricFallbackWarning)
        rep = pa.report()
    assert rep.parametric["status"] == "symbolic"
    assert rep.params["N"] == 16 and rep.params["T"] == 8
    assert _dumps(report_payload(pa.evaluate(N=16, T=8))) == _dumps(
        _concrete_payload(case, {"N": 16, "T": 8}))
    pa.release()


# ---------------------------------------------------------------- sweep axis

def test_sweep_sizes_axis_matches_concrete_cfg_major():
    case = get("gemm")
    cfgs = [dict(case.tilings),
            {name: Tiling(t.normals, tuple(2 * b for b in t.sizes), t.offsets)
             for name, t in case.tilings.items()}]
    # sizes on both templates' lattices (strides 4 and 8 from base 12)
    sizes = [20, 28, 36]
    with warnings.catch_warnings():
        warnings.simplefilter("error", ParametricFallbackWarning)
        reports = sweep(case.kernel, cfgs, sizes={"N": sizes},
                        stages=("classify", "fifoize", "size"))
    assert len(reports) == len(cfgs) * len(sizes)
    i = 0
    for cfg in cfgs:
        for n in sizes:
            a = analyze(case.kernel, params={"N": n}, tilings=cfg)
            conc = report_payload(a.classify().fifoize().size().report())
            assert _dumps(report_payload(reports[i])) == _dumps(conc), (
                f"cfg={cfg}, N={n}")
            i += 1


# --------------------------------------------------- sympy cross-validation

def test_closed_forms_cross_validate_with_sympy(gemm_pa):
    sympy = pytest.importorskip("sympy")
    # sympify("N**2") would resolve N to sympy's numeric-eval function
    syms = {"N": sympy.Symbol("N")}
    for name, poly in gemm_pa.closed_forms().items():
        expr = sympy.sympify(str(poly), locals=syms)
        for n in (12, 17, 31, 100):
            assert expr.subs(syms["N"], n) == poly(N=n), (name, n)
    doc = gemm_pa.report().parametric
    total = sympy.sympify(doc["total_capacity"]["capacity"], locals=syms)
    parts = sum(sympy.sympify(s["capacity"], locals=syms)
                for s in doc["sizes"].values())
    assert sympy.simplify(total - parts) == 0
