"""autoconfigure: lattice legality, constraints, provenance, surfaces.

Runs the planner once on the LeNet-5 smoke build (module fixture,
briefly trained so accuracy against the float reference is meaningful —
tests/_nets.py) and probes the searched plan from every surface: the
search result itself, ``plan.compile`` -> Executable,
``api.autoconfigure``, ``Accelerator.compile(auto=...)`` and the
serve_cnn CLI validation.
"""

import numpy as np
import pytest

from _nets import trained_lenet
from repro import api
from repro.core import conversion
from repro.launch import serve_cnn
from repro.ppa import search

FLOOR, SLO = 0.6, 5000.0
KW = dict(accuracy_floor=FLOOR, latency_slo_us=SLO,
          t_range=(3, 4), units=(2, 4))


@pytest.fixture(scope="module")
def lenet_net():
    return trained_lenet("avg", calib_batch=32)


@pytest.fixture(scope="module")
def plan(lenet_net):
    static, params, item, calib = lenet_net
    return search.autoconfigure((static, params), item, calib=calib, **KW)


def test_winner_satisfies_constraints(plan):
    w = plan.winner
    assert w is not None and w.feasible
    assert w.accuracy >= FLOOR
    assert w.ppa.latency_us <= SLO
    assert w in plan.frontier
    assert len(plan.frontier) >= 1


def test_rejection_provenance_recorded(plan):
    rejected = [c for c in plan.candidates if not c.feasible]
    assert rejected, "smoke LeNet under a 0.6 floor must prune ttfs/T=3"
    # every rejection names its reason; accuracy prunes carry the value
    for c in rejected:
        assert c.rejected and all(r for r in c.rejected)
    assert any("accuracy" in r for c in rejected for r in c.rejected)


def test_accuracy_evaluated_once_per_spec(plan):
    legal_specs = {c.spec for c in plan.candidates if c.backend != "-"}
    assert plan.accuracy_evals == len(legal_specs)
    # all candidates of one spec share the accuracy number
    for spec in legal_specs:
        accs = {c.accuracy for c in plan.candidates if c.spec == spec}
        assert len(accs) == 1


def test_frontier_is_nondominated(plan):
    for c in plan.frontier:
        assert not any(search._dominates(o, c) for o in plan.frontier
                       if o is not c)


def test_objective_latency_picks_fastest(lenet_net):
    static, params, item, calib = lenet_net
    p = search.autoconfigure((static, params), item, calib=calib,
                             objective="latency", **KW)
    assert p.winner.ppa.latency_us == min(
        c.ppa.latency_us for c in p.frontier)


def test_summary_and_to_dict(plan):
    s = plan.summary()
    assert "winner:" in s and "rejected" in s and "constraints:" in s
    d = plan.to_dict()
    assert d["winner"]["accuracy"] == plan.winner.accuracy
    assert len(d["rejected"]) == sum(
        1 for c in plan.candidates if not c.feasible)
    assert d["n_candidates"] == len(plan.candidates)


def test_or_pooling_rejects_rate_and_ttfs_at_spec_level():
    static, params, item, calib = trained_lenet("or", calib_batch=8)
    p = search.autoconfigure((static, params), item, calib=calib,
                             accuracy_floor=0.01, t_range=(3,), units=(2,))
    spec_level = {c.spec.name: c for c in p.candidates if c.backend == "-"}
    assert {"rate", "ttfs"} <= set(spec_level)
    for c in spec_level.values():
        assert c.units == 0 and c.ppa is None
        assert any("illegal for this net" in r for r in c.rejected)
    # radix still wins on the or-pool net
    assert p.winner is not None and p.winner.spec.name == "radix"


def test_infeasible_floor_yields_no_winner(lenet_net):
    static, params, item, calib = lenet_net
    p = search.autoconfigure((static, params), item, calib=calib,
                             accuracy_floor=2.0, t_range=(3,), units=(2,))
    assert p.winner is None and p.frontier == []
    assert all(not c.feasible for c in p.candidates)
    with pytest.raises(ValueError, match="no feasible configuration"):
        p.compile()


def test_input_validation(lenet_net):
    static, params, item, calib = lenet_net
    qnet = conversion.convert(static, params, calib, num_steps=4)
    with pytest.raises(TypeError, match="QuantizedNet"):
        search.autoconfigure(qnet, item, calib=calib, accuracy_floor=0.5)
    with pytest.raises(TypeError, match="pair"):
        search.autoconfigure(42, item, calib=calib, accuracy_floor=0.5)
    with pytest.raises(ValueError, match="objective"):
        search.autoconfigure((static, params), item, calib=calib,
                             accuracy_floor=0.5, objective="area")
    with pytest.raises(ValueError, match="non-empty"):
        search.autoconfigure((static, params), item, calib=calib,
                             accuracy_floor=0.5, t_range=())
    with pytest.raises(ValueError, match="calib item shape"):
        search.autoconfigure((static, params), (8, 8, 3), calib=calib,
                             accuracy_floor=0.5)


def test_plan_compile_round_trip(plan, lenet_net):
    _, _, item, calib = lenet_net
    exe = plan.compile(buckets=(4,))
    assert exe.encoding == plan.winner.spec
    assert exe.backend == plan.winner.backend
    out = np.asarray(exe(calib[:4]))
    assert out.shape == (4, 10)
    ppa = exe.stats()["ppa"]
    assert ppa["latency_us"] == pytest.approx(plan.winner.ppa.latency_us)
    assert ppa["energy_uj"] == pytest.approx(plan.winner.ppa.energy_uj)


def test_api_facade_matches_search(lenet_net):
    static, params, item, calib = lenet_net
    p = api.autoconfigure((static, params), item, calib=calib,
                          accuracy_floor=0.5, t_range=(3,), units=(2,))
    assert p.winner is not None
    assert isinstance(p, search.AutoPlan)


def test_accelerator_compile_auto(lenet_net):
    static, params, item, calib = lenet_net
    exe = api.Accelerator().compile(
        (static, params), item,
        auto=dict(calib=calib, accuracy_floor=0.5, t_range=(3,),
                  units=(2,)), buckets=(2,))
    assert exe.auto_plan.winner is not None
    assert exe.encoding == exe.auto_plan.winner.spec
    out = np.asarray(exe(calib[:2]))
    assert out.shape == (2, 10)


def test_accelerator_compile_auto_conflicts(lenet_net):
    static, params, item, calib = lenet_net
    auto = dict(calib=calib, accuracy_floor=0.5)
    with pytest.raises(ValueError, match="dataflow"):
        api.Accelerator(dataflow="fused").compile((static, params), item,
                                                  auto=auto)
    with pytest.raises(ValueError, match="encoding"):
        api.Accelerator().compile((static, params), item, auto=auto,
                                  encoding=api.RadixEncoding(4))


# ---------------------------------------------------------------------------
# serve_cnn CLI validation (the planner flags)
# ---------------------------------------------------------------------------


def _parse(extra):
    return serve_cnn._parse_args(["--arch", "lenet5", "--smoke"] + extra)


def test_cli_auto_defaults():
    args = _parse(["--auto"])
    assert args.auto and args.accuracy_floor == 0.9
    assert args.latency_slo is None and args.energy_budget is None


def test_cli_auto_owns_the_planner_axes(capsys):
    for flag in (["--encoding", "ttfs"], ["--num-steps", "4"],
                 ["--dataflow", "fused"], ["--backend", "jnp"],
                 ["--periods", "2"]):
        with pytest.raises(SystemExit):
            _parse(["--auto"] + flag)
        assert "conflicts with --auto" in capsys.readouterr().err


def test_cli_constraints_require_auto(capsys):
    for flag in (["--accuracy-floor", "0.9"], ["--latency-slo", "100"],
                 ["--energy-budget", "50"]):
        with pytest.raises(SystemExit):
            _parse(flag)
        assert "requires --auto" in capsys.readouterr().err


def test_cli_constraint_ranges(capsys):
    for flag in (["--accuracy-floor", "1.5"], ["--accuracy-floor", "0"],
                 ["--latency-slo", "-1"], ["--energy-budget", "0"]):
        with pytest.raises(SystemExit):
            _parse(["--auto"] + flag)
    args = _parse(["--auto", "--accuracy-floor", "0.7",
                   "--latency-slo", "800", "--energy-budget", "2500"])
    assert (args.accuracy_floor, args.latency_slo,
            args.energy_budget) == (0.7, 800.0, 2500.0)
