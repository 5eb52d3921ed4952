"""Persistent serving compile cache (mxnet_tpu.serving.cache): the
warm-start contracts.

* A second replica warming from the same cache directory DESERIALIZES
  every bucket — zero XLA compiles (stats counter AND the process
  CompileWatch), served rows bitwise equal to the cold replica.
* Every key-mismatch path falls back loudly to a fresh compile instead
  of serving a stale executable: drifted params digest (architecture
  change), cross-precision-mode entry, different backend signature,
  tampered/truncated entries, crashed ``.tmp-*`` partials (never
  loadable — the checkpoint atomic-commit idiom).
* Warmup accounting: per-bucket ``warmup_ms`` gauges, cache hit/miss
  counters in both the serving scope and the ``compile.*`` scope, and
  warmup traces attributed to ``compile.warmup_compiles`` — never the
  training ``compile.retraces`` stream.
"""
import glob
import os

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.serving import Predictor
from mxnet_tpu.serving.cache import (CacheMiss, ExecutableCache,
                                     cache_key)

DIM = 6


def _net(hidden=16):
    # every layer explicitly named: the params digest covers the symbol
    # JSON, and auto-named layers take process-global counters — two
    # builds of "the same" net would then disagree (a fresh replica
    # process starts its counters at zero, so real deployments match)
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=hidden, name="fc1")
    net = sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = sym.Activation(net, act_type="relu", name="relu1")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, DIM).astype(np.float32),
            rng.randint(0, 10, n).astype(np.float32))


def _train_module(hidden=16, precision=None):
    mx.random.seed(7)
    kwargs = {"precision": precision} if precision else {}
    mod = mx.mod.Module(_net(hidden), context=[mx.cpu()], **kwargs)
    X, y = _data()
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=8), num_epoch=1,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    return mod


@pytest.fixture(scope="module")
def trained():
    mod = _train_module()
    X, _ = _data()
    ref = mod.predict(mx.io.NDArrayIter(X, None, batch_size=8)).asnumpy()
    return mod, X, ref


def _entries(cache_dir):
    return sorted(os.path.basename(p) for p in
                  glob.glob(os.path.join(cache_dir, "aot", "*.mxexec")))


# ---------------------------------------------------------------------
# warm start: zero compiles, bitwise parity
# ---------------------------------------------------------------------
def test_cold_then_warm_bitwise_and_zero_compiles(tmp_path, trained):
    mod, X, ref = trained
    cache_dir = str(tmp_path / "cache")
    watch = mx.telemetry.compile_watch()

    cold = Predictor(mod, max_batch_size=8)
    retraces0 = watch.count
    s1 = cold.warmup(cache_dir=cache_dir)
    # cold replica: every bucket compiled (a miss), entry committed
    assert s1["compiles"] == len(cold.buckets)
    assert s1["cache_misses"] == len(cold.buckets)
    assert s1["cache_hits"] == 0
    assert len(_entries(cache_dir)) == len(cold.buckets)
    # warmup traces are their own compile.* stream, NOT retraces
    assert watch.count == retraces0
    cold_out = {n: cold.predict(X[:n]) for n in (1, 3, 5, 8, 13)}
    for n, out in cold_out.items():
        assert np.array_equal(out, ref[:n]), n

    warm = Predictor(mod, max_batch_size=8)
    retraces1, warmups1 = watch.count, watch.warmup_compiles
    s2 = warm.warmup(cache_dir=cache_dir)
    # the warm-start contract: zero XLA compiles across the ladder,
    # pinned by the serving counter AND the CompileWatch wrapper
    assert s2["compiles"] == 0
    assert s2["cache_hits"] == len(warm.buckets)
    assert s2["cache_misses"] == 0
    assert watch.count == retraces1
    assert watch.warmup_compiles == warmups1
    rep = warm.warmup_report()
    assert set(rep) == set(warm.buckets)
    assert all(r["source"] == "deserialized" for r in rep.values())
    # served rows bitwise equal to the cold-start replica
    for n, out in cold_out.items():
        assert np.array_equal(warm.predict(X[:n]), out), n
    # steady traffic through the deserialized programs compiles nothing
    for n in (2, 6, 11, 16):
        warm.predict(X[:n])
    assert warm.stats()["compiles"] == 0


def test_rewarmup_after_eviction_recompiles(tmp_path, trained):
    """Re-calling warmup(cache_dir=) on an already-warm Predictor after
    an operator wiped the entries must fall back to a fresh compile of
    the (deserialized, non-re-lowerable) installed executable — not
    crash — and recommit the entries."""
    import shutil
    mod, X, ref = trained
    cache_dir = str(tmp_path / "cache")
    Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    warm = Predictor(mod, max_batch_size=4)
    warm.warmup(cache_dir=cache_dir)
    assert all(r["source"] == "deserialized"
               for r in warm.warmup_report().values())
    shutil.rmtree(os.path.join(cache_dir, "aot"))
    s = warm.warmup(cache_dir=cache_dir)
    assert all(r["source"] == "compiled"
               for r in warm.warmup_report().values())
    assert s["cache_misses"] >= len(warm.buckets)
    assert len(_entries(cache_dir)) == len(warm.buckets)
    assert np.array_equal(warm.predict(X[:3]), ref[:3])


def test_warmup_gauges_and_compile_scope_counters(tmp_path, trained):
    mod, _X, _ref = trained
    watch = mx.telemetry.compile_watch()
    hits0, misses0 = watch.cache_hits, watch.cache_misses
    cache_dir = str(tmp_path / "cache")
    pred = Predictor(mod, max_batch_size=4)
    s = pred.warmup(cache_dir=cache_dir)
    # per-bucket compile/deserialize wall time: snapshot + gauges
    assert set(s["warmup_ms"]) == set(pred.buckets)
    assert all(ms > 0 for ms in s["warmup_ms"].values())
    gauges = mx.telemetry.registry().snapshot()["gauges"]
    scope = pred._stats.scope.prefix
    for b in pred.buckets:
        assert "%s.b%d.warmup_ms" % (scope, b) in gauges
    assert watch.cache_misses == misses0 + len(pred.buckets)
    warm = Predictor(mod, max_batch_size=4)
    warm.warmup(cache_dir=cache_dir)
    assert watch.cache_hits == hits0 + len(warm.buckets)
    # compile.cache_hits rides the shared registry for export
    counters = mx.telemetry.registry().snapshot()["counters"]
    assert counters.get("compile.cache_hits", 0) >= len(warm.buckets)


def test_classic_warmup_unchanged_without_cache_dir(trained):
    mod, X, ref = trained
    pred = Predictor(mod, max_batch_size=4)
    s = pred.warmup()
    assert s["compiles"] == len(pred.buckets)
    assert s["cache_hits"] == 0 and s["cache_misses"] == 0
    assert all(r["source"] == "jit"
               for r in pred.warmup_report().values())
    assert np.array_equal(pred.predict(X[:3]), ref[:3])


# ---------------------------------------------------------------------
# key-mismatch refusals (the loud-fallback contract)
# ---------------------------------------------------------------------
def test_params_digest_drift_refuses_entries(tmp_path, trained):
    mod, _X, _ref = trained
    cache_dir = str(tmp_path / "cache")
    Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    n_before = len(_entries(cache_dir))
    # same bucket ladder, DIFFERENT architecture: the digest drifts and
    # every entry is refused — fresh compiles, new entries committed
    other = _train_module(hidden=24)
    pred = Predictor(other, max_batch_size=4)
    s = pred.warmup(cache_dir=cache_dir)
    assert s["cache_hits"] == 0
    assert s["cache_misses"] == len(pred.buckets)
    assert s["compiles"] == len(pred.buckets)
    assert len(_entries(cache_dir)) == n_before + len(pred.buckets)
    # ... and each architecture still warm-hits its OWN entries
    again = Predictor(other, max_batch_size=4)
    s2 = again.warmup(cache_dir=cache_dir)
    assert s2["cache_hits"] == len(again.buckets)
    assert s2["compiles"] == 0


def test_cross_precision_mode_refused(tmp_path):
    f32_mod = _train_module()
    cache_dir = str(tmp_path / "cache")
    Predictor(f32_mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    # same architecture under a bf16 policy: the mode name keys the
    # entry, so the f32 executable is never adopted
    bf16_mod = _train_module(precision="bf16")
    pred = Predictor(bf16_mod, max_batch_size=4)
    s = pred.warmup(cache_dir=cache_dir)
    assert s["cache_hits"] == 0
    assert s["cache_misses"] == len(pred.buckets)
    # the f32 replica still hits its own entries afterwards
    s2 = Predictor(f32_mod, max_batch_size=4).warmup(
        cache_dir=cache_dir)
    assert s2["cache_hits"] == len(pred.buckets)


def test_backend_signature_mismatch_is_a_miss(tmp_path, trained):
    mod, _X, _ref = trained
    pred = Predictor(mod, max_batch_size=4)
    cache_dir = str(tmp_path / "cache")
    pred.warmup(cache_dir=cache_dir)
    store = ExecutableCache(os.path.join(cache_dir, "aot"))
    grp = pred._modules[pred.buckets[0]]._exec_group
    key = pred._bucket_cache_key(grp, pred.buckets[0])
    store.load(key)  # sanity: the real key loads
    drifted = cache_key(key["params_digest"], key["precision_mode"],
                        key["bucket"], key["input_sig"],
                        key["backend_sig"] + ";jax=9.9.9")
    with pytest.raises(CacheMiss) as e:
        store.load(drifted)
    assert e.value.reason == "key-mismatch"
    assert "backend_sig" in e.value.detail


# ---------------------------------------------------------------------
# corrupt / truncated / .tmp-* entries
# ---------------------------------------------------------------------
def _one_entry(cache_dir):
    paths = glob.glob(os.path.join(cache_dir, "aot", "*.mxexec"))
    assert paths
    return paths[0]


def test_tampered_entry_recompiles_and_heals(tmp_path, trained):
    mod, X, ref = trained
    cache_dir = str(tmp_path / "cache")
    Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    path = _one_entry(cache_dir)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:      # flip a payload byte: crc fails
        f.write(blob[:-10] + bytes([blob[-10] ^ 0xFF]) + blob[-9:])
    pred = Predictor(mod, max_batch_size=4)
    s = pred.warmup(cache_dir=cache_dir)
    assert s["cache_misses"] >= 1      # the tampered bucket recompiled
    assert s["cache_hits"] == len(pred.buckets) - s["cache_misses"]
    assert np.array_equal(pred.predict(X[:3]), ref[:3])
    # the fresh compile overwrote the bad entry: next replica all-hits
    s2 = Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    assert s2["cache_hits"] == len(pred.buckets)


def test_truncated_entry_refused(tmp_path, trained):
    mod, _X, _ref = trained
    cache_dir = str(tmp_path / "cache")
    pred = Predictor(mod, max_batch_size=4)
    pred.warmup(cache_dir=cache_dir)
    path = _one_entry(cache_dir)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    store = ExecutableCache(os.path.join(cache_dir, "aot"))
    refused = 0
    for b in pred.buckets:
        key = pred._bucket_cache_key(
            pred._modules[b]._exec_group, b)
        try:
            store.load(key)
        except CacheMiss as e:
            assert e.reason == "corrupt", e
            refused += 1
    assert refused == 1
    s = Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    assert s["cache_misses"] == 1


def test_tmp_partials_never_loadable(tmp_path, trained):
    mod, _X, _ref = trained
    cache_dir = str(tmp_path / "cache")
    pred = Predictor(mod, max_batch_size=4)
    pred.warmup(cache_dir=cache_dir)
    aot = os.path.join(cache_dir, "aot")
    # a successful commit leaves no .tmp-* partial behind
    assert not glob.glob(os.path.join(aot, ".tmp-*"))
    # simulate a crash mid-commit: the entry exists only as .tmp-*
    path = _one_entry(cache_dir)
    os.rename(path, os.path.join(aot, ".tmp-%s-deadbeef"
                                 % os.path.basename(path)))
    store = ExecutableCache(aot)
    assert not any(n.startswith(".tmp-") for n in store.entries())
    missing = 0
    for b in pred.buckets:
        key = pred._bucket_cache_key(pred._modules[b]._exec_group, b)
        try:
            store.load(key)
        except CacheMiss as e:
            assert e.reason == "absent", e
            missing += 1
    assert missing == 1
    # warmup recompiles the lost bucket instead of touching the partial
    s = Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    assert s["cache_misses"] == 1
    assert s["cache_hits"] == len(pred.buckets) - 1


# ---------------------------------------------------------------------
# digest threading: checkpoint manifest <-> predictor
# ---------------------------------------------------------------------
def test_manifest_records_params_digest(tmp_path, trained):
    mod, X, ref = trained
    manager = mx.checkpoint.CheckpointManager(str(tmp_path / "ckpt"))
    mod.save_checkpoint(None, 1, manager=manager, async_save=False)
    extra = manager.step_metadata(1)
    pred = Predictor(mod, max_batch_size=4)
    assert extra["params_digest"] == pred.params_digest
    # a manager-restored module carries the digest and serves cleanly
    restored = Predictor.load(str(tmp_path / "ckpt"),
                              data_shapes=[("data", (8, DIM))],
                              max_batch_size=4)
    assert restored.params_digest == pred.params_digest
    restored.warmup()
    assert np.array_equal(restored.predict(X[:3]), ref[:3])


def test_post_load_param_swap_refused(tmp_path, trained):
    mod, _X, _ref = trained
    manager = mx.checkpoint.CheckpointManager(str(tmp_path / "ckpt"))
    mod.save_checkpoint(None, 1, manager=manager, async_save=False)
    loaded = mx.mod.Module.load(str(tmp_path / "ckpt"))
    # swap the restored params for a different architecture's: the
    # manifest digest no longer matches what the module would serve
    other = _train_module(hidden=24)
    arg, aux = other.get_params()
    loaded._arg_params, loaded._aux_params = arg, aux
    with pytest.raises(mx.MXNetError, match="params digest"):
        Predictor(loaded, data_shapes=[("data", (8, DIM))],
                  max_batch_size=4)


def test_cache_shared_across_checkpoints_of_one_architecture(
        tmp_path, trained):
    """Parameter VALUES are runtime inputs: two checkpoints of the
    same architecture share executables (same digest), so a weight
    refresh warm-starts too."""
    mod, _X, _ref = trained
    cache_dir = str(tmp_path / "cache")
    Predictor(mod, max_batch_size=4).warmup(cache_dir=cache_dir)
    mx.random.seed(11)
    retrained = mx.mod.Module(_net(), context=[mx.cpu()])
    X, y = _data(seed=3)
    retrained.fit(mx.io.NDArrayIter(X, y, batch_size=8), num_epoch=1,
                  optimizer="sgd")
    pred = Predictor(retrained, max_batch_size=4)
    s = pred.warmup(cache_dir=cache_dir)
    assert s["cache_hits"] == len(pred.buckets)
    assert s["compiles"] == 0
    ref = retrained.predict(
        mx.io.NDArrayIter(X, None, batch_size=8)).asnumpy()
    assert np.array_equal(pred.predict(X[:5]), ref[:5])


# ---------------------------------------------------------------------
# a one-device replica in a process that sees eight devices
# ---------------------------------------------------------------------
def test_one_device_replicas_warm_and_serve_among_eight(tmp_path, trained):
    """jax 0.9's ``deserialize_and_load`` defaults ``execution_devices``
    to EVERY device of the backend; a one-device executable loaded that
    way fails at first launch ("expected 8 shards"). Both warm paths
    name their own devices — pinned on devices that are not device 0."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine, LSTMCharLM
    devs = jax.devices()
    assert len(devs) == 8
    mod, X, ref = trained
    cache_dir = str(tmp_path / "cache")

    def replica():
        p = Predictor(mod, max_batch_size=8, context=[mx.tpu(3)])
        p.warmup(cache_dir=cache_dir)
        return p

    cold, warm = replica(), replica()
    assert {r["source"] for r in warm.warmup_report().values()} \
        == {"deserialized"}
    grp = warm._modules[8]._exec_group
    assert list(grp.mesh.devices.flat) == [devs[3]]
    for n in (1, 5, 8):
        assert np.array_equal(warm.predict(X[:n]), ref[:n]), n
    cold.release()
    warm.release()

    model = LSTMCharLM(vocab_size=16, num_hidden=8, num_embed=4)
    params = model.init_params(seed=3)

    def engine():
        # the engine lands where a bare device_put lands: jax's
        # default device
        with jax.default_device(devs[5]):
            eng = DecodeEngine(model, params, slots=2, max_prefill_len=4,
                               start=False)
            report = eng.warmup(cache_dir=cache_dir)
        assert eng._device == devs[5]
        try:
            eng.start()
            return eng.generate([1, 2, 3], max_new_tokens=6,
                                timeout=120), report
        finally:
            eng.shutdown(drain=True)
            eng.release()

    first, _ = engine()
    second, report = engine()
    assert {r["source"] for r in report.values()} == {"deserialized"}
    assert first == second and len(first) == 6


# ---------------------------------------------------------------------
# the jax compile cache is placed from outside, or at one fixed path
# ---------------------------------------------------------------------
def test_jax_compile_cache_dir_is_never_overridden(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it itself and no
    code path here sets another directory — not the entry-point
    function, not MXNET_COMPILE_CACHE_DIR (which names only the AOT
    store), not a warmup. Unset, the one function points jax at
    <checkout>/.jax_cache; nothing else in the tree touches the knob."""
    import re
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setters = []
    for base in ("mxnet_tpu", "tools", "example"):
        for dirpath, _, files in os.walk(os.path.join(root, base)):
            setters += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    setters += [os.path.join(root, f) for f in
                ("chip_smoke.py", "__graft_entry__.py")]
    setters = [os.path.relpath(p, root) for p in setters
               if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                            open(p).read())]
    assert setters == [os.path.join("mxnet_tpu", "serving", "cache.py")]

    code = (
        "import jax, numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.serving import enable_persistent_compile_cache\n"
        "from mxnet_tpu.serving.decode import DecodeEngine, LSTMCharLM\n"
        "print('import', jax.config.jax_compilation_cache_dir)\n"
        "print('returned', enable_persistent_compile_cache())\n"
        "m = LSTMCharLM(vocab_size=8, num_hidden=4, num_embed=2)\n"
        "e = DecodeEngine(m, m.init_params(seed=0), slots=1,\n"
        "                 max_prefill_len=4, start=False)\n"
        "e.warmup()\n"
        "print('after', jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "aot_root"))

    def run(extra):
        out = subprocess.run([sys.executable, "-c", code], cwd=root,
                             env=dict(env, **extra), text=True,
                             capture_output=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return dict(ln.split(" ", 1) for ln in out.stdout.splitlines()
                    if ln.split(" ", 1)[0] in ("import", "returned",
                                               "after"))

    given = str(tmp_path / "given")
    got = run({"JAX_COMPILATION_CACHE_DIR": given})
    assert got == {"import": given, "returned": given, "after": given}
    assert _entries(str(tmp_path / "aot_root"))     # the AOT store only

    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    fixed = os.path.join(root, ".jax_cache")
    got = run({})
    assert got == {"import": "None", "returned": fixed, "after": fixed}
