"""Segmented-remat evaluator (executor.py _build_eval_segmented):
numerics must match the plain evaluator exactly — outputs, gradients,
and BatchNorm aux updates — since Module(remat=...) swaps it in for
training. Also asserts the checkpoint structure is really present
(remat in the grad jaxpr) and a Module-level A/B on the fused path."""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.executor import _build_eval, _build_eval_segmented
from test_afmoe import TINY as AFMOE_TINY


def _bn_net():
    net = sym.Variable("data")
    net = sym.Convolution(net, kernel=(3, 3), num_filter=4, pad=(1, 1),
                          name="c1")
    net = sym.BatchNorm(net, name="bn1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Convolution(net, kernel=(3, 3), num_filter=4, pad=(1, 1),
                          name="c2")
    net = sym.BatchNorm(net, name="bn2")
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


def test_segmented_matches_plain_with_bn_aux():
    import jax
    import jax.numpy as jnp

    net = _bn_net()
    arg_names = net.list_arguments()
    aux_names = net.list_auxiliary_states()
    shapes, _, aux_shapes = net.infer_shape(data=(4, 2, 8, 8),
                                            softmax_label=(4,))
    rng = np.random.RandomState(0)
    args = [rng.rand(*s).astype(np.float32) * 0.5 for s in shapes]
    auxs = [np.zeros(s, np.float32) if "mean" in n else
            np.ones(s, np.float32)
            for n, s in zip(aux_names, aux_shapes)]
    key = jax.random.PRNGKey(7)

    plain, _ = _build_eval(net)
    seg, _ = _build_eval_segmented(net, "full", n_segments=3)

    p_out, p_aux = jax.jit(lambda a, x, r: plain(a, x, r, True))(
        args, auxs, key)
    s_out, s_aux = jax.jit(lambda a, x, r: seg(a, x, r, True))(
        args, auxs, key)
    for a, b in zip(p_out, s_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # BN moving stats updated identically through the checkpoint
    for n, a, b in zip(aux_names, p_aux, s_aux):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=n)
        if "mean" in n:  # genuinely updated, not passed through
            assert float(np.abs(np.asarray(a)).sum()) > 0

    # gradients wrt every arg match
    def loss(ev):
        def f(vals):
            outs, _ = ev(vals, auxs, key, True)
            return jnp.sum(outs[0] * outs[0])
        return f

    gp = jax.jit(jax.grad(loss(plain)))(args)
    gs = jax.jit(jax.grad(loss(seg)))(args)
    for n, a, b in zip(arg_names, gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5, err_msg=n)


def test_segmented_dropout_stream_matches_plain():
    """rng threading through segments reproduces the plain evaluator's
    per-op key sequence — identical dropout masks."""
    import jax

    net = sym.Variable("data")
    net = sym.Dropout(net, p=0.5, name="do1")
    net = sym.FullyConnected(net, num_hidden=8, name="fc")
    net = sym.Dropout(net, p=0.5, name="do2")
    net = sym.Group([net])
    rng = np.random.RandomState(1)
    args = [rng.rand(*s).astype(np.float32) + 0.5
            for s in net.infer_shape(data=(4, 8))[0]]
    key = jax.random.PRNGKey(3)

    plain, _ = _build_eval(net)
    seg, _ = _build_eval_segmented(net, "full", n_segments=2)
    p_out, _ = jax.jit(lambda a, r: plain(a, [], r, True))(args, key)
    s_out, _ = jax.jit(lambda a, r: seg(a, [], r, True))(args, key)
    np.testing.assert_allclose(np.asarray(p_out[0]),
                               np.asarray(s_out[0]), rtol=1e-6)


def test_module_remat_matches_plain_training():
    """Module(remat='full') must train to the same numbers as
    remat=None (pure recompute, no math change)."""
    from mxnet_tpu.io import NDArrayIter

    rng = np.random.RandomState(0)
    X = rng.rand(64, 2, 8, 8).astype(np.float32)
    y = rng.randint(0, 3, 64).astype(np.float32)

    def train(remat):
        np.random.seed(0)
        it = NDArrayIter(X, y, batch_size=16,
                         label_name="softmax_label")
        mod = mx.mod.Module(_bn_net(), remat=remat)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        # a classic-group fallback would silently test plain-vs-plain
        assert getattr(mod._exec_group, "fused", False), \
            "remat A/B requires the fused mesh path"
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for _ in range(2):
            it.reset()
            for b in it:
                mod.forward_backward(b)
                mod.update()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    a = train(None)
    b = train("full")
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=1e-5,
                                   err_msg=k)


def test_segmented_jaxpr_contains_checkpoints():
    """The recompute structure must actually be present: remat/checkpoint
    primitives in the gradient jaxpr of the segmented evaluator (a
    degenerate single-segment or dropped-checkpoint regression would
    still pass the numeric tests)."""
    import jax
    import jax.numpy as jnp

    net = _bn_net()
    shapes, _, aux_shapes = net.infer_shape(data=(4, 2, 8, 8),
                                            softmax_label=(4,))
    rng = np.random.RandomState(0)
    args = [rng.rand(*s).astype(np.float32) * 0.5 for s in shapes]
    auxs = [np.zeros(s, np.float32) for s in aux_shapes]
    key = jax.random.PRNGKey(0)
    seg, _ = _build_eval_segmented(net, "full", n_segments=3)

    def loss(vals):
        outs, _ = seg(vals, auxs, key, True)
        return jnp.sum(outs[0])

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(args))
    assert "remat" in jaxpr or "checkpoint" in jaxpr, \
        "segmented evaluator lost its checkpoint structure"
    # the last segment's backward pass follows its forward pass at
    # once: a checkpoint there would only run it a second time
    assert jaxpr.count("remat") + jaxpr.count("checkpoint") == 2, \
        "expected one checkpoint a segment but the last"


# ------------------------------------------------------------------
# what a segment's backward pass re-runs: the cheap, not the dear
# ------------------------------------------------------------------
TINY = dict(AFMOE_TINY, layer_types=["sliding_attention", "full_attention"])
ROWS = 32
# the FullyConnected nodes of the two layers, by width; `head` apart: it
# lies in the last segment (47 op nodes: 7 segments of 7, the last of 5)
PRODUCTS = {"l%d_%s" % (i, n): w for i in (0, 1) for n, w in
            [("q", 32), ("k", 16), ("v", 16), ("gate", 32), ("o", 32)]}
PRODUCTS.update(l0_mlp_gate=48, l0_mlp_up=48, l0_mlp_down=32,
                l1_shared_gate=16, l1_shared_up=16, l1_shared_down=32)
# products no backward pass makes again under any policy: a segment's
# last node read by the next segment alone (a boundary value, held
# anyway), and one that only an addition reads (whose gradient needs it
# not)
NEVER_AGAIN = {"l0_k", "l0_mlp_down", "l1_shared_down"}
ATTENTION_BYTES = 2 * (2 * 4 * 16 * 8) * 4      # two layers' (B, H, T, D) f32
PRODUCT_BYTES = ROWS * sum(PRODUCTS.values()) * 4


def _strict():
    import jax
    return jax.checkpoint_policies.nothing_saveable


def _tiny_decoder():
    """The two-layer decoder (a dense layer, an expert layer), its
    argument values and auxiliary state."""
    from mxnet_tpu import models
    net = models.get_symbol("afmoe", **TINY)
    shapes, _, aux_shapes = net.infer_shape(data=(ROWS,),
                                            softmax_label=(ROWS,))
    rs = np.random.RandomState(0)
    args = [rs.randint(0, 64, s).astype(np.float32)
            if n in ("data", "softmax_label")
            else (rs.randn(*s) * 0.1).astype(np.float32)
            for n, s in zip(net.list_arguments(), shapes)]
    return net, args, [np.zeros(s, np.float32) for s in aux_shapes]


def _eqns(jaxpr, out=None):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, out)
    return out


def _runs(eqns, primitive, node):
    """How many `primitive` equations the symbol node `node` traced to,
    forward, recomputed and backward together."""
    at = re.compile(r"(^|[/(])%s([/)]|$)" % re.escape(node))
    return sum(1 for e in eqns if e.primitive.name == primitive
               and at.search(str(e.source_info.name_stack)))


def _grad_eqns(net, args, auxs, remat, kept=None):
    import jax
    import jax.numpy as jnp
    ev, _ = _build_eval_segmented(net, remat)
    key = jax.random.PRNGKey(0)

    def loss(vals):
        outs, _ = ev(vals, auxs, key, True, kept=kept)
        return jnp.sum(outs[0] * outs[0])

    return _eqns(jax.make_jaxpr(jax.grad(loss))(args).jaxpr), loss


def test_full_keeps_the_products_and_attention_and_remakes_the_norms(capsys):
    """Under "full" a wrapped segment's backward pass is handed each
    FullyConnected's product and attention's output (a product runs
    forward once, then its two backward products) and makes the norms
    again; the strict callable makes the products again too."""
    from jax.ad_checkpoint import print_saved_residuals
    net, args, auxs = _tiny_decoder()
    full, loss = _grad_eqns(net, args, auxs, "full")
    strict, strict_loss = _grad_eqns(net, args, auxs, _strict())
    for node in PRODUCTS:
        assert _runs(full, "dot_general", node) == 3, node
        assert _runs(strict, "dot_general", node) \
            == (3 if node in NEVER_AGAIN else 4), node
    for eqns in (full, strict):
        # the last segment is not wrapped: nothing in it runs twice
        assert _runs(eqns, "dot_general", "head") == 3
        assert _runs(eqns, "rsqrt", "final_norm") == 1
        for norm in ("l0_in_norm", "l0_q_norm", "l1_pre_mlp_norm"):
            assert _runs(eqns, "rsqrt", norm) == 2, norm
    assert _runs(full, "dot_general", "l0_attn") \
        < _runs(strict, "dot_general", "l0_attn")

    # what is kept, as jax itself lists it: beside the arguments, one
    # value for every attention and every product of a wrapped segment
    # that a backward pass reads
    print_saved_residuals(loss, args)
    lines = capsys.readouterr().out.splitlines()
    named = [ln for ln in lines if ln.rstrip().endswith("(keep)")]
    assert len(named) == len(PRODUCTS) - len(NEVER_AGAIN) + 2
    assert sum("f32[2,4,16,8]" in ln for ln in named) == 2    # attention
    assert sum("from the argument" in ln for ln in lines) > 30
    # strictly, only what leaves a segment is held (two of the products)
    print_saved_residuals(strict_loss, args)
    held = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.rstrip().endswith("(keep)")]
    assert len(held) == 2 and not any("f32[2,4,16,8]" in ln for ln in held)


def test_strict_callable_reaches_the_program_through_module():
    """`Module(remat=jax.checkpoint_policies.nothing_saveable)` is the
    escape to "a segment keeps nothing": its step program runs a
    product forward twice where `remat="full"`'s runs it once."""
    import jax
    net, _, _ = _tiny_decoder()

    def step_eqns(remat):
        mod = mx.mod.Module(net, context=[mx.cpu(0)], remat=remat)
        mod.bind(data_shapes=[("data", (ROWS,))],
                 label_shapes=[("softmax_label", (ROWS,))])
        mod.init_params(mx.initializer.Xavier())
        eg = mod._exec_group
        assert eg.fused
        spec = lambda d: {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for n, v in d.items()}
        params = spec({n: b._read() for n, b in eg._param_dict.items()})
        aux = spec({n: b._read() for n, b in eg._aux_dict.items()})
        inputs = {n: jax.ShapeDtypeStruct((ROWS,), np.float32)
                  for n in ("data", "softmax_label")}
        rng = jax.ShapeDtypeStruct((2,), np.uint32)
        return _eqns(jax.make_jaxpr(eg._get_jit("fwd_bwd"))(
            params, aux, inputs, rng).jaxpr), eg

    full, eg = step_eqns("full")
    assert eg._remat_kept_bytes == PRODUCT_BYTES + ATTENTION_BYTES
    strict, eg = step_eqns(_strict())
    assert eg._remat_kept_bytes == 0
    for node in ("l0_q", "l1_o", "l1_shared_up"):
        assert _runs(full, "dot_general", node) == 3, node
        assert _runs(strict, "dot_general", node) == 4, node


@pytest.mark.parametrize("remat,names", [
    ("full", ("product", "attention")), ("dots", ("attention",)),
    ("bn_stats", ("attention",)), ("strict", ())])
def test_kept_bytes_are_the_named_values_by_shape(remat, names):
    """`kept` takes the bytes of the values the policy keeps by name
    inside the wrapped segments: the head's logits (last segment) are
    no part, and a callable, which decides for itself, reports none."""
    net, args, auxs = _tiny_decoder()
    kept = {}
    eqns, _ = _grad_eqns(net, args, auxs,
                         _strict() if remat == "strict" else remat, kept)
    by_shape = {"product": PRODUCT_BYTES, "attention": ATTENTION_BYTES}
    assert kept == {n: by_shape[n] for n in names}
    # "dots" keeps attention's output on the blockwise path, where its
    # own policy sees only products: the blocks' forward products are
    # not made a third time
    strict, _ = _grad_eqns(net, args, auxs, _strict())
    if names:
        assert _runs(eqns, "dot_general", "l1_attn") \
            < _runs(strict, "dot_general", "l1_attn")
    else:
        assert _runs(eqns, "dot_general", "l1_attn") \
            == _runs(strict, "dot_general", "l1_attn")


def test_bn_stats_are_counted_where_their_policy_keeps_them():
    import jax
    import jax.numpy as jnp
    net = _bn_net()
    shapes, _, aux_shapes = net.infer_shape(data=(4, 2, 8, 8),
                                            softmax_label=(4,))
    args = [np.full(s, 0.5, np.float32) for s in shapes]
    auxs = [np.zeros(s, np.float32) for s in aux_shapes]
    got = {}
    for remat in ("bn_stats", "dots", "full"):
        ev, _ = _build_eval_segmented(net, remat, n_segments=3)
        kept = got[remat] = {}
        jax.make_jaxpr(jax.grad(lambda v: jnp.sum(ev(
            v, auxs, jax.random.PRNGKey(0), True, kept=kept)[0][0])))(args)
    # bn1 lies in the first segment, bn2 in the second (wrapped both):
    # a mean and a variance of 4 channels each; `fc` is in the last
    assert got == {"bn_stats": {"bn_stats": 2 * 2 * 4 * 4}, "dots": {},
                   "full": {}}
