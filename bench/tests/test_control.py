"""The control at a tiny size on the CPU: the float8 reference's first
choices sit further below the float32 reference's best than the tokens
the program served."""

import importlib.util
import os

import pytest

import tiny


@pytest.fixture(scope="module")
def control():
    spec = importlib.util.spec_from_file_location(
        "bench_control", os.path.join(tiny.BENCH, "control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_control_reads_above_the_program(control, kind):
    import jax
    r = control.readings(tiny.load_run(), tiny.spec(kind), 12345, 1.5, jax,
                         log=lambda *a: None)
    assert r["tokens"] > 0
    assert r["gap"] <= tiny.LIMIT < r["control_gap"], r
    assert r["correct"] and not r["control_correct"], r
