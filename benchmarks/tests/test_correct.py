"""`correct` at sizes a test can hold: the float32 program passes the cell's
own limits, the control (the reference with fp8 matmuls, put in the program's
place) does not, and a run whose timed path is broken underneath comes out
false."""

import io
import json
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import check, harness
from benchmarks.tests import tiny

CELLS = {"r2d2-fused": ("fused_r2d2", tiny.r2d2_fields, "freeway-16lanes")}


def _driver_class(cell):
    import importlib

    return importlib.import_module(
        "benchmarks.drivers." + CELLS[cell][0]).Driver


def _tiny_factory(cell, cls):
    _, fields, traffic = CELLS[cell]
    return lambda _f, _t, seed, chips, **kw: cls(
        fields(), tiny.traffic(traffic), seed, 1, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_program_passes_and_control_fails(cell):
    limits = tiny.load("workloads", cell)["limits"]
    exact = {"window_steps_missing": 0.0, "first_steps_missing": 0.0}
    drv = _tiny_factory(cell, _driver_class(cell))(None, None, 5, 1)
    drv.warm_up()
    prog = drv.program_side()
    ref = drv.reference_side(None, prog["priority_after"] != drv.priority0())
    sound, rows = check.verdict(
        {**check.compare(prog, ref, drv.params0), **exact}, limits)
    assert sound, rows
    control = drv.reference_side("fp8", None)
    ok, rows = check.verdict(
        {**check.compare(control, ref, drv.params0), **exact}, limits)
    assert not ok, rows


def _state_unchanged(cls):
    """The driver with its learn steps' parameter update thrown away."""

    class Broken(cls):
        def build(self):
            super().build()
            real = self.segment

            def segment(carry, key):
                kept = jax.tree.map(jnp.copy, carry[0].params)
                carry, outs = real(carry, key)
                return (carry[0].replace(params=kept), *carry[1:]), outs

            self.segment = segment

    return Broken


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_broken_timed_path_is_not_correct(cell):
    results = {}
    for name, cls in (("sound", _driver_class(cell)),
                      ("broken", _state_unchanged(_driver_class(cell)))):
        out = io.StringIO()
        rc = harness.run(cell, 2**31 + 3, 0.5, False, t0=time.perf_counter(),
                         devices=jax.devices()[:1],
                         make_driver=_tiny_factory(cell, cls), out=out)
        assert rc == 0
        results[name] = json.loads(out.getvalue().splitlines()[-1])
    assert results["sound"]["correct"] is True
    assert results["broken"]["correct"] is False
    assert list(results["sound"])[-1] == "compared"


def test_a_number_read_and_not_compared_needs_no_limit_and_fails_nothing():
    ok, rows = check.verdict({"a": 0.5, "b": 9.0}, {"a": 1.0}, ("b",))
    assert ok and [name for name, _, _ in rows] == ["a"]
    assert not check.verdict({"a": 0.5, "b": 9.0}, {"a": 1.0})[0]
    assert not check.verdict({"b": 9.0}, {"a": 1.0}, ("b",))[0]
    with pytest.raises(ValueError):
        check.verdict({"a": 0.5}, {"a": 1.0}, ("a",))
