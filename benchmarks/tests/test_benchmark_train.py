"""The training driver at a tiny size on the CPU: the added cells run
and are correct, a step that returns its state unchanged is caught, the
four-device cell checks every device, and the fp8 control breaks the
limits that the bf16 program keeps."""

import json
import os

import numpy as np
import pytest

from benchmarks.tests import helpers

# a train step that applies nothing: parameters and velocity come back
# as they went in
BREAK_STEP = """
from bigdl_tpu.optim import optim_method as _om
def _unchanged(self, grad, param, state):
    new = dict(state)
    new["neval"] = state["neval"] + 1.0
    return param, new
_om.SGD.step = _unchanged
"""

# a trainer that leaves out a part of the batch: the second half of
# every batch repeats the first
BREAK_BATCH = """
import numpy as np
from bigdl_tpu.optim import optimizer as _o
_orig = _o.LocalOptimizer._put_batch
def _half(self, inp, tgt):
    inp, tgt = np.array(inp), np.array(tgt)
    h = len(inp) // 2
    inp[h:], tgt[h:] = inp[:h], tgt[:h]
    return _orig(self, inp, tgt)
_o.LocalOptimizer._put_batch = _half
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_added_training_cell_runs_and_is_correct(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_train", seed=2**31 + 3,
                                       seconds=1.5)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["metrics"]["train_samples_per_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "check compiles_of_the_step: 1 == 1 ok" in out
    assert "compiled inside the window" not in out


def test_four_device_cell_holds_every_device_to_the_same_parameters(copy):
    rc, result, out = helpers.rehearse(copy, "tiny_distri", seconds=1.5,
                                       chips=4)
    assert rc == 0, out
    assert result["correct"] is True, out
    assert result["device"]["count"] == 4
    assert "check parameters_unequal_across_chips: 0 == 0 ok" in out
    assert "check chips_holding_parameters: 4 == 4 ok" in out


@pytest.mark.parametrize("fault,number", [
    (BREAK_STEP, "parameter_change_norm_gap"),
    (BREAK_BATCH, "loss_gap"),
])
def test_a_broken_timed_path_comes_out_not_correct(copy, fault, number):
    rc, result, out = helpers.rehearse(copy, "tiny_train", seconds=1.0,
                                       before=fault)
    assert rc == 0, out
    assert result["correct"] is False, out
    failed = [ln for ln in out.splitlines() if ln.endswith("FAILED")]
    assert any(number in ln for ln in failed), failed


def test_the_fp8_control_breaks_a_limit_the_reference_keeps():
    from benchmarks.lib import traffic
    from benchmarks.reference import resnet50_imagenet as ref

    with open(os.path.join(helpers.DATA, "tiny_resnet.json")) as fh:
        config = json.load(fh)
    limit = config["limits"]["first_gradient_difference_max"]
    readings = []
    for seed in (1, 2, 3):
        x, y = traffic.train_data({"batch": 8, "host_batches": 3}, seed,
                                  config["image_size"],
                                  config["num_classes"])
        batches = [(x[i * 8:(i + 1) * 8], y[i * 8:(i + 1) * 8])
                   for i in range(3)]
        p0 = ref.init_params(seed, config)
        want = ref.follow(p0, batches, config, 0.01, 0.9)
        ctl = ref.follow(p0, batches, config, 0.01, 0.9, precision="fp8")
        again = ref.follow(p0, batches, config, 0.01, 0.9)
        assert ref.difference(again["first_gradient"],
                              want["first_gradient"]) == 0.0
        readings.append(ref.difference(ctl["first_gradient"],
                                       want["first_gradient"]))
    print("fp8 control, first_gradient_difference:", readings)
    assert min(readings) > limit


def test_program_tree_mapping_round_trips():
    from benchmarks.reference import resnet50_imagenet as ref

    with open(os.path.join(helpers.DATA, "tiny_resnet.json")) as fh:
        config = json.load(fh)
    p = ref.init_params(9, config)
    assert len(p) == 161
    assert sum(v.size for v in p.values()) == 23_528_522  # 10 classes
    back = ref.from_program_tree(ref.to_program_tree(p, config), config)
    assert set(back) == set(p)
    assert all(back[k] is p[k] for k in p)
    gaps = ref.norm_gaps({k: 2 * v for k, v in p.items()}, p)
    assert max(gaps.values()) == pytest.approx(1.0)
    assert np.isclose(min(gaps.values()), 0.0)  # gamma-0 leaves: 0 vs 0
