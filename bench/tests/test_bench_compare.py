"""The exact reference agrees with the program's own Dinic, certifies the
min cut tightly, and the comparison refuses spoiled cuts and the
control."""
import numpy as np
import pytest

from bench import compare, instances
from bench.control import bf16
from bench.reference.maxflow import Reference, cut_value
from repro.core import max_flow
from repro.graphs.structures import EdgeList, STInstance


def _inst(family, side, seed=0):
    return instances.build({"family": family, "side": side, "seed": seed})


def _answer(inst, side, value=None):
    w = (inst.weight, inst.s_weight, inst.t_weight)
    if value is None:
        value = cut_value(inst.src, inst.dst, *w, side)
    return w, side, value


@pytest.mark.parametrize("family,side,seed", [("road", 20, 0), ("road", 24, 3),
                                              ("grid3d", 5, 0),
                                              ("grid3d", 6, 2)])
def test_reference_matches_program_dinic(family, side, seed):
    inst = _inst(family, side, seed)
    ref = Reference(inst.n, inst.src, inst.dst).solve(
        inst.weight, inst.s_weight, inst.t_weight)
    st = STInstance(EdgeList(inst.src, inst.dst, inst.weight, inst.n),
                    inst.s_weight, inst.t_weight)
    dinic = max_flow(st)
    assert ref.lower <= dinic.value * (1 + 1e-12)
    assert abs(ref.upper - dinic.value) <= 1e-9 * dinic.value
    assert (ref.upper - ref.lower) / ref.lower < 1e-8


@pytest.mark.parametrize("family,side", [("road", 24), ("grid3d", 6)])
def test_exact_cut_passes_and_spoiled_cuts_fail(family, side):
    inst = _inst(family, side)
    ref = Reference(inst.n, inst.src, inst.dst)
    exact = ref.solve(inst.weight, inst.s_weight, inst.t_weight).in_source
    ok, _ = compare.judge(compare.compare(ref, [_answer(inst, exact)], 0))
    assert ok
    flipped = exact.copy()
    flipped[np.argmax(inst.s_weight + inst.t_weight)] ^= True
    for ans in (_answer(inst, flipped),
                _answer(inst, exact, value=1.001 * cut_value(
                    inst.src, inst.dst, inst.weight, inst.s_weight,
                    inst.t_weight, exact)),
                _answer(inst, exact[:-1], value=1.0)):
        ok, checks = compare.judge(compare.compare(ref, [ans], 0))
        assert not ok, checks


@pytest.mark.parametrize("family,side", [("road", 120), ("grid3d", 16)])
def test_bfloat16_reference_in_place_is_not_correct(family, side):
    """The control at a size a test run holds: the reference's cut on
    capacities rounded to bfloat16 fails the comparison."""
    inst = _inst(family, side)
    ref = Reference(inst.n, inst.src, inst.dst)
    low = [bf16(a) for a in (inst.weight, inst.s_weight, inst.t_weight)]
    side_low = ref.solve(*low).in_source
    numbers = compare.compare(ref, [(
        (inst.weight, inst.s_weight, inst.t_weight), side_low,
        cut_value(inst.src, inst.dst, *low, side_low))], 0)
    ok, checks = compare.judge(numbers)
    assert not ok, checks


def test_judge_refuses_a_missing_number():
    """A number that the caller leaves out fails loudly, not as correct."""
    with pytest.raises(KeyError):
        compare.judge({"cut_gap": 0.0})
