"""Integration tier: the example-family scripts run end to end (synthetic
data) and hit their built-in learning asserts. Mirrors the reference's
example smoke coverage (tests/python/train + examples run in CI).

Each script asserts its own success criterion (accuracy/MSE/return), so
a pass here means the family genuinely trains, not just imports.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cases NOT owned by a scenario: either no pinned-workload scenario
# mirrors them, or their flags are harness-specific. Scenario-owned
# invocations (mxnet_tpu.scenarios registry, `example=` field) are
# appended below so the example smoke and the scenario matrix can
# never drift apart on how a long-tail script is invoked.
CASES = [
    ("autoencoder/autoencoder.py", ["--num-epoch", "15"]),
    ("adversary/fgsm.py", ["--num-epoch", "5"]),
    ("multi-task/multitask.py", ["--num-epoch", "25"]),
    ("svm_mnist/svm_mnist.py", ["--num-epoch", "8"]),
    ("numpy-ops/custom_softmax.py", ["--num-epoch", "5"]),
    ("recommenders/matrix_fact.py", ["--num-epoch", "15"]),
    ("gan/gan_mnist.py", ["--num-iter", "500"]),
    ("cnn_text_classification/text_cnn.py", ["--num-epoch", "6"]),
    ("bi-lstm-sort/sort_lstm.py", ["--num-epoch", "8"]),
    ("reinforcement-learning/reinforce.py", ["--episodes", "250"]),
    ("fcn-xs/fcn_xs.py", ["--num-epoch", "8"]),
    ("stochastic-depth/sto_depth.py", ["--num-epoch", "12"]),
    ("module/mnist_mlp.py", []),
    ("image-classification/fine_tune.py", []),
    ("image-classification/train_cifar10.py",
     ["--num-epochs", "3"]),
    # precision mode (mxnet_tpu.precision): bf16 optimizer state +
    # dots_saveable remat through the full fit path; the script's
    # --min-accuracy assert doubles as the mode's accuracy gate (the
    # within-mode digest-reproducibility contract runs in ci.sh)
    ("image-classification/train_cifar10.py",
     ["--num-epochs", "3", "--opt-state-dtype", "bf16",
      "--remat", "dots_saveable", "--min-accuracy", "0.9"]),
    # chaos smoke (mxnet_tpu.faults): a seeded plan injects transient
    # staging faults through the prefetch path; the shared retry heals
    # them and the script asserts every planned rule actually fired
    # (the bitwise digest-vs-fault-free compare runs in ci.sh)
    ("image-classification/train_cifar10.py",
     ["--num-epochs", "1", "--seed", "7", "--prefetch-device", "2",
      "--fault-plan",
      "data.device_put:transient@nth=5;data.stager:transient@nth=9"]),
    # training guardian (mxnet_tpu.guardian): a planned NaN batch
    # mid-train is detected by the device health sentinel, healed by
    # rollback-and-skip, and the run completes; the script asserts the
    # rollback actually happened (the bitwise parity contract runs in
    # ci.sh / tests/test_guardian.py)
    ("image-classification/train_cifar10.py",
     ["--num-epochs", "2", "--seed", "11", "--guardian",
      "--fault-plan", "module.step:grad_nonfinite@epoch=1,nbatch=3"]),
    ("neural-style/neural_style.py", ["--iters", "200"]),
    ("warpctc/ctc_train.py", ["--num-epoch", "10"]),
    ("bayesian-methods/sgld.py",
     ["--steps", "2000", "--burn-in", "500"]),
    ("dec/dec.py", ["--pretrain-epochs", "8"]),
    ("memcost/memcost.py",
     ["--width", "16", "--img", "32", "--batch-size", "32"]),
    ("rnn-time-major/rnn_cell_demo.py", ["--num-epoch", "6"]),
    ("torch/torch_module.py", ["--num-epoch", "12"]),
    ("torch/torch_module.py",
     ["--num-epoch", "12", "--use-torch-criterion"]),
    ("speech_recognition/deepspeech_mini.py", ["--num-epoch", "25"]),
    ("rcnn/train_rcnn.py",
     ["--num-epochs", "2", "--num-examples", "64", "--batch-size", "8"]),
    ("caffe/train_caffe_net.py", ["--num-epoch", "4"]),
    ("model-parallel-lstm/lstm.py",
     ["--num-epoch", "3", "--seq-len", "8", "--num-hidden", "32"]),
    ("rnn/char_lstm.py",
     ["--num-epoch", "3", "--seq-len", "16", "--num-hidden", "64"]),
    # continuous-batching decode serving (mxnet_tpu.serving.decode):
    # trains the unfused char-LM via fit, adopts the params into the
    # slot-structured DecodeEngine, and self-asserts module/engine
    # argmax parity, learned-text continuation, bitwise stream parity
    # vs unbatched decode, and the continuous > sequential tokens/sec
    # win (the full seeded witness runs in ci.sh / dryrun_decode)
    ("rnn/decode_lm.py",
     ["--num-epochs", "3", "--seq-len", "16", "--num-hidden", "64"]),
    # weight-only int8 decode (mxnet_tpu.precision.quant): the same
    # decode demo served through precision="int8_weight" — the script
    # additionally asserts the compiled step program's analyzed
    # argument bytes shrink vs the f32 engine (the memory-bound decode
    # win) while parity/continuation/throughput asserts still hold
    # (the full seeded witness runs in ci.sh / dryrun_quant)
    ("rnn/decode_lm.py",
     ["--num-epochs", "3", "--seq-len", "16", "--num-hidden", "64",
      "--int8-weights"]),
    ("profiler/profiler_demo.py",
     ["--iter-num", "5", "--size", "128",
      "--output", "/tmp/profiler_demo_ci.json"]),
    ("moe/train_moe.py", ["--epochs", "10"]),
    ("python-howto/multiple_outputs.py", []),
    ("python-howto/data_iter.py", []),
    ("python-howto/monitor_weights.py", []),
    ("python-howto/debug_conv.py", []),
    ("kaggle-ndsb1/train_dsb.py", ["--synthetic", "--num-epoch", "15",
      "--submission", "/tmp/submission_ci.csv"]),
    ("kaggle-ndsb2/train.py", ["--synthetic", "--num-epoch", "25"]),
    ("speech-demo/train_timit.py", ["--num-epoch", "15"]),
    ("image-classification/train_imagenet.py",
     ["--network", "resnet-18", "--image-shape", "3,64,64",
      "--batch-size", "16", "--synthetic-images", "64",
      "--num-epochs", "2"]),
    ("image-classification/serve_cifar10.py",
     ["--num-epochs", "1", "--clients", "4", "--requests", "8",
      "--max-batch-size", "16"]),
    # the scoring loop and its completion barrier, per launch and
    # with batch_group batches a launch (a smoke: the CPU's rate is
    # no device number)
    ("image-classification/benchmark_score.py",
     ["--networks", "resnet-18", "--batch-size", "2",
      "--num-batches", "4"]),
    ("image-classification/benchmark_score.py",
     ["--networks", "resnet-18", "--batch-size", "2",
      "--num-batches", "4", "--batch-group", "2"]),
    # provisions its own 8-device virtual CPU platform (it is a
    # multi-host demo; the harness's 1-device env is overridden inside)
    ("distributed-training/elastic_virtual_hosts.py",
     ["--num-epochs", "3"]),
]


def _scenario_cases():
    """Scenario-owned example invocations: every registered scenario
    that pins an example/ script contributes exactly the invocation
    the scenario registry declares (docs/api/scenarios.md). Includes
    the u8 device-augment + cached-dataset cifar case (cnn_u8_cache),
    nce-loss (nce_loss), the bucketing LSTM (bucketing_lstm), and the
    toy SSD (ssd_toy)."""
    from mxnet_tpu.scenarios import registry
    return [(s.example[0], list(s.example[1]))
            for s in registry.scenarios() if s.example is not None]


CASES = CASES + _scenario_cases()


@pytest.mark.parametrize("script,args",
                         CASES, ids=[c[0].split("/")[0] for c in CASES])
def test_example_trains(script, args):
    path = os.path.join(ROOT, "example", script)
    # single CPU device: examples tune their hyperparameters for one
    # device; under the suite's 8-way virtual mesh the tiny per-device
    # batches change training dynamics (multi-chip correctness has its
    # own tier — test_module_fused / dryrun_multichip)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-u", path] + args,
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, (
        "%s failed:\n%s\n%s" % (script, proc.stdout[-2000:],
                                proc.stderr[-2000:]))


def test_serve_warm_start_flow(tmp_path):
    """Serving warm-start flow (docs/api/serving.md "Persistent
    compile cache"): serve_cifar10 --cache-dir cold-warms the ladder
    (compile + atomic entry commit), then its in-script "second
    replica" (fresh Predictor, fresh jit objects) must deserialize
    every bucket with zero XLA compiles and serve bitwise-equal rows.
    Per-run tmp cache dir — the true two-process warm start
    (--expect-warm + response-digest compare) is the ci.sh gate."""
    path = os.path.join(ROOT, "example",
                        "image-classification", "serve_cifar10.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-u", path, "--num-epochs", "1",
         "--clients", "4", "--requests", "8", "--max-batch-size", "16",
         "--cache-dir", str(tmp_path / "cache")],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        "serve_cifar10 --cache-dir failed:\n%s\n%s"
        % (proc.stdout[-2000:], proc.stderr[-2000:]))
    assert "second replica warm-started" in proc.stdout, \
        proc.stdout[-2000:]


def test_transformer_lm_tp_on_mesh():
    """Module-reachable tensor parallelism: the transformer LM trains
    through Module.fit on a dp=2 x tp=4 mesh (example/transformer-lm/)
    with Megatron-sharded block weights, hitting its accuracy assert."""
    path = os.path.join(ROOT, "example", "transformer-lm",
                        "transformer_lm_tp.py")
    proc = subprocess.run(
        [sys.executable, "-u", path, "--num-epoch", "10"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        "transformer_lm_tp failed:\n%s\n%s"
        % (proc.stdout[-2000:], proc.stderr[-2000:]))


def test_ring_attention_lm_on_mesh():
    """Long-context example: ring attention over the suite's 8-device
    virtual mesh — exact-match vs full attention plus the long-range
    copy-task learning assert (example/long-context/)."""
    path = os.path.join(ROOT, "example", "long-context",
                        "ring_attention_lm.py")
    proc = subprocess.run(
        [sys.executable, "-u", path, "--steps", "600"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        "ring_attention_lm failed:\n%s\n%s"
        % (proc.stdout[-2000:], proc.stderr[-2000:]))
