"""What decides `correct`, at a size a test run can hold (a few
bottleneck units, 48x48 input, batch 8, float32 on the CPU):

* the reference against the program: one run of the harness, chip look
  skipped, agrees to rounding;
* the control (the reference computed in fp8) comes out not correct
  under every cell's limits;
* the harness sees `correct` false with the timed path broken
  underneath, once for each fault a training cell can have.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check, harness, traffic, weights  # noqa: E402
from benchmark.reference import resnet as reference  # noqa: E402

CFG = {"family": "resnet", "units": [2, 1, 1, 1], "filters": [16, 32, 64, 128, 256],
       "classes": 10, "image": [3, 48, 48], "per_chip_batch": 8,
       "symbol_call": {"function": "mxnet_tpu.models.resnet.resnet",
                       "arguments": {"units": [2, 1, 1, 1], "num_stages": 4,
                                     "filter_list": [16, 32, 64, 128, 256],
                                     "num_classes": 10,
                                     "image_shape": (3, 48, 48),
                                     "bottle_neck": True}},
       "compute_dtype": None,
       "optimizer": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9,
                     "wd": 1e-4}}
RESIDENT = {"feed": "resident", "distinct_batches": 4, "steps_per_epoch": 3,
            "warm_steps": 2}
RECORDIO = {"feed": "recordio", "records": 48, "distinct_images": 8,
            "jpeg_quality": 90, "warm_steps": 2,
            "iterator": {"shuffle": True, "rand_mirror": True,
                         "mean_r": 123.68, "mean_g": 116.28, "mean_b": 103.53,
                         "preprocess_threads": 2,
                         "label_name": "softmax_label"}}
CELLS = ["resnet50-b256.fit-resident", "resnet50-b256.fit-recordio"]


def _limits(cell):
    with open(os.path.join(ROOT, "benchmark", "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def _run(cell, mix, seed=7, cfg=CFG):
    code, result = harness.run(ROOT, cell, seed, 0.3, False,
                               time.perf_counter(), cfg_mix=(dict(cfg), mix),
                               require_chip=False)
    assert code == 0
    return result


@pytest.fixture(autouse=True)
def exact_statistics(monkeypatch):
    # the program's default one-pass BatchNorm variance rounds
    # differently from the two-pass form at these tiny maps (32 values
    # a channel); its exact form shows the reference is a transcription
    monkeypatch.setenv("MXNET_BN_EXACT_STATS", "1")


@pytest.mark.parametrize("cell,mix", [(CELLS[0], RESIDENT),
                                      (CELLS[1], RECORDIO)])
def test_reference_agrees_with_the_program(cell, mix):
    result = _run(cell, mix)
    got = {k: v["value"] for k, v in result["check"].items()}
    assert result["correct"] is True, got
    for name in ("loss1_gap", "loss2_gap", "loss3_gap", "grad_gap",
                 "change_gap", "stat_gap"):
        assert got[name] < 2e-4, (name, got[name])
    for name in ("grad_shrink", "change_shrink"):
        assert abs(got[name]) < 2e-4, (name, got[name])
    assert got["window_compiles"] == 0
    assert list(result)[-1] == "check"
    assert result["failed"] == 0 and result["attempted"] > 0
    if mix is RECORDIO:
        # cv2 and PIL decode one JPEG to the same pixels, or nearly
        assert got["input_gap"] <= _limits(cell)["input_gap"]


def _reference_readings(arith, rows=None):
    import jax
    arch = reference.arch_of(CFG)
    args, aux = weights.make(11, *reference.param_shapes(arch))
    batches = traffic.own_batches(RESIDENT, CFG, 11, 1, harness.CHECK_STEPS)
    if rows is not None:
        batches = [(x[:rows], y[:rows]) for x, y in batches]
    opt = {k: CFG["optimizer"][k] for k in ("learning_rate", "momentum", "wd")}
    return reference.follow(args, aux, batches, arch, opt, arith=arith)


@pytest.fixture(scope="module")
def readings():
    return {"f32": _reference_readings(reference.Exact),
            "fp8": _reference_readings(check.Fp8),
            "half": _reference_readings(reference.Exact,
                                        rows=CFG["per_chip_batch"] // 2)}


def _judged(got, want, cell):
    """`got` in the program's place, under the cell's limits; what the
    reference does not produce (the rows' own gap) reads as sound."""
    numbers = check.compare(got, want,
                            reference.products(reference.arch_of(CFG)))
    numbers["window_compiles"] = (0, "")
    if "input_gap" in _limits(cell):
        numbers["input_gap"] = (0.0, "")
    return check.judge(numbers, dict(_limits(cell), window_compiles=0))


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, readings):
    """The reference computed in fp8, put in the program's place."""
    ok, rows = _judged(readings["fp8"], readings["f32"], cell)
    assert not ok, rows
    same, rows = _judged(readings["f32"], readings["f32"], cell)
    assert same, rows


@pytest.mark.parametrize("cell", CELLS)
def test_half_a_batch_is_not_correct(cell, readings):
    ok, rows = _judged(readings["half"], readings["f32"], cell)
    assert not ok, rows


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from mxnet_tpu import optimizer
    monkeypatch.setattr(optimizer.SGD, "_fused_apply",
                        lambda self, jnp, p, g, s, lr, wd: (p, s))
    result = _run(CELLS[0], RESIDENT)
    assert result["correct"] is False
    assert result["check"]["change_gap"]["value"] == pytest.approx(1.0)
    assert result["check"]["change_median_gap"]["value"] > 0.9


def _stage_only(keep):
    """The executor group's staging with every row block replaced by
    the first `1/keep` of the batch: what is left when the other rows
    (or the other chips' share of the exchange) are left out."""
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup
    staged = MeshExecutorGroup._stage

    def stage(self, batch, is_train=False):
        import jax
        import jax.numpy as jnp
        inputs = staged(self, batch, is_train)
        out = {}
        for name, v in inputs.items():
            part = v[:v.shape[0] // keep]
            out[name] = jax.device_put(
                jnp.concatenate([part] * keep, axis=0), v.sharding)
        return out
    return stage


def test_half_the_batch_left_out_of_a_run_is_not_correct(monkeypatch):
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup
    monkeypatch.setattr(MeshExecutorGroup, "_stage", _stage_only(2))
    result = _run(CELLS[0], RESIDENT)
    assert result["correct"] is False


@pytest.mark.parametrize("cell,mix", [(CELLS[0], RESIDENT),
                                      (CELLS[1], RECORDIO)])
def test_moving_statistics_left_unchanged_are_not_correct(monkeypatch, cell,
                                                          mix):
    """The step goes on training and no longer writes BatchNorm's
    moving statistics back: every other number stays sound (a training
    step normalises by the batch's own statistics), so this one alone
    has to fail the run."""
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup
    monkeypatch.setattr(MeshExecutorGroup, "_write_aux",
                        lambda self, new_aux: None)
    result = _run(cell, mix)
    got = {k: v["value"] for k, v in result["check"].items()}
    assert result["correct"] is False
    assert got["stat_median_gap"] > 0.9
    failed = {k for k, v in result["check"].items()
              if v["limit"] is not None and not v["value"] <= v["limit"]}
    assert failed == {"stat_median_gap"}, got


def test_altered_pixels_are_not_correct(monkeypatch):
    from mxnet_tpu import image
    decode = image.imdecode
    monkeypatch.setattr(image, "imdecode",
                        lambda buf, to_rgb=True: decode(buf, to_rgb) // 2)
    result = _run(CELLS[1], RECORDIO)
    assert result["correct"] is False
    assert result["check"]["input_gap"]["value"] > 10
