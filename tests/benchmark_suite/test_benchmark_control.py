"""The control of ``correct`` at a size a test run can hold: the comparison
passes for the program and FAILS one precision step down: the engine's own
int8 path, and the reference itself computed in float8 and put in the
program's place. The chip's readings at the cells' own sizes are in PERF.md.
"""

import jax
import pytest

from benchmark import control, correctness
from benchmark import manifest as mf

SEEDS = [5, 2**31 + 6, 77]


@pytest.mark.parametrize("config,traffic,controls", [
    ("rehearsal-tiny", "rehearsal-open", ("reference_fp8", "program_int8")),
    ("rehearsal-tiny-moe", "rehearsal-closed",
     ("reference_fp8", "program_int8")),
    # Another architecture, through its own reference. The program's int8
    # path reads 0.0034-0.0042 at these widths beside a sound 0.0032-0.0038
    # (twelve seeds, CPU): it separates nothing here, so the reference in
    # float8 (0.036-0.041) is this fixture's control.
    ("rehearsal-tiny-gemma", "rehearsal-open", ("reference_fp8",))])
def test_serving_comparison_fails_one_precision_step_down(config, traffic,
                                                          controls):
    conf = mf.load_json(f"benchmark/configs/{config}.json")
    limits = conf["correctness"]["limits"]
    tr = mf.load_traffic(traffic)
    sound, low = [], []
    for seed in SEEDS:
        sides = control.serving_sides(conf, tr, seed,
                                      ["program", *controls])
        ok, _ = correctness.judge(sides["program"], limits)
        assert ok, sides["program"]
        for side in controls:
            ok, lines = correctness.judge(sides[side], limits)
            assert not ok, (side, lines)
            for name in limits:          # each number fails by itself
                assert sides[side][name] > limits[name], (side, name)
        sound += [sides["program"][n] for n in limits]
        low += [sides["reference_fp8"][n] for n in limits]
    assert min(low) > 3 * max(sound)


def test_training_comparison_fails_one_precision_step_down():
    conf = mf.load_json("benchmark/configs/rehearsal-tiny-fsdp4.json")
    limits = conf["correctness"]["limits"]
    tr = mf.load_traffic("rehearsal-train")
    for seed in SEEDS:
        side = control.training_sides(conf, tr, seed,
                                      jax.devices()[:4])["reference_fp8"]
        ok, lines = correctness.judge(side, limits)
        assert not ok, lines


def test_position_errors_and_judge():
    import numpy as np

    want = np.array([[1.0, -1.0, 0.0, 0.0], [2.0, 0.0, 0.0, -2.0]], np.float32)
    got = want + np.array([[0.1, 0, 0, 0], [0, 0, 0, 0]], np.float32)
    err = correctness.position_errors(got, want)
    assert err[0] == pytest.approx(0.1 / np.sqrt(2.0), rel=1e-5)
    assert err[1] == 0.0
    ok, lines = correctness.judge({"a": 0.5, "b": float("nan")},
                                  {"a": 1.0, "b": 1.0})
    assert not ok and "OVER" in lines[1] and "ok" in lines[0]
    assert correctness.judge({"a": 0.5}, {"a": 0.5})[0]
