"""The control of the correctness check, at a size a test run can hold: the
reference put in the program's place with its weight projections in float8
(the nearest precision below the bfloat16 inputs a float32 product gets on
a TPU), compared with the reference as a run compares, fails the limits of
the benchmark's cells, where the float32 program passes them."""

import pytest

from benchharness import cpu_devices, load, tiny_cell

LIMITS = ["sc2-train-4k-1chip", "nemo-train-4k-2x2"]


@pytest.mark.parametrize("chips", [1, 4])
def test_fp8_control_fails_the_limits(chips):
    train = load("benchmark/drivers/train.py")
    cell = tiny_cell(chips=chips)
    config, traffic = cell["config"], cell["traffic"]
    devices = cpu_devices(chips)
    seed = 2 ** 31 + 7
    ref = train.reference_readings(config, traffic, seed, devices)
    control = train.reference_readings(config, traffic, seed, devices,
                                       matmul="fp8")
    prog = train.Program(config, traffic, devices)
    _, r = prog.first_steps(seed, prog.batches(seed))
    readings = {"program": train.compare(r, ref),
                "control": train.compare(control, ref)}
    for limits_of in LIMITS:
        limits = tiny_cell(limits_of=limits_of)["limits"]
        assert all(v <= limits[k] for k, v in readings["program"].items()
                   if k in limits), readings
        assert any(v > limits[k] for k, v in readings["control"].items()
                   if k in limits), readings
