"""The comparison that decides `correct`, and the control's operand.

What the timed path produced in its first steps is held against the
plain reference on the same weights and rows.  Every number is a gap
of norms, never the norm of a difference (`compare`), each with a
limit of its own read from `benchmark/limits/<cell>.json`.
"""
import statistics

import jax
import jax.numpy as jnp


def _leaf_gaps(got, want, keep=None):
    """By leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns (worst gap, its leaf, median gap)."""
    names = sorted(k for k in want if keep is None or k in keep)
    median = statistics.median(want[k] for k in names)
    gaps = [abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in names]
    worst, where = 0.0, None
    for k, gap in zip(names, gaps):
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = gap, k
    if any(g != g for g in gaps):
        return worst, where, float("nan")
    return worst, where, statistics.median(gaps)


def moved_leaves(ref):
    """Leaves the reference's first gradient moves: those whose norm
    is at least a thousandth of the median leaf's.  The others (a
    scale in front of a BatchNorm, which takes it out again) have no
    gradient but round-off, and change by round-off alone."""
    g = ref["grad_norm"]
    floor = 1e-3 * statistics.median(g.values())
    return {k for k, v in g.items() if v >= floor}


def _shrink(got, want, names):
    """How much smaller the program's norms are than the reference's,
    at the median of the leaves `names`: minus the median signed gap.
    Round-to-nearest in the stated type scatters the norms both ways
    and leaves this near nought; a type with too few exponent bits
    flushes small values and shrinks every product, so it reads the
    same sign on every seed."""
    return -statistics.median(
        (got[k] - want[k]) / max(want[k], 1e-30) for k in sorted(names))


def compare(program, ref, products):
    """{number: (reading, where)}: each step's loss; for the first
    gradient and for the parameters' change the worst leaf's gap, the
    median leaf's, and the shrink at the median product weight (the
    leaves `products`, operands of a convolution or a matmul); for the
    moving statistics' change the worst and the median leaf's gap.
    PERF.md says which carry a limit, and why."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"])):
        out["loss%d_gap" % (i + 1)] = (abs(a - b) / abs(b), "step %d" % (i + 1))
    moved = moved_leaves(ref)
    for name, key, keep in (("grad", "grad_norm", moved),
                            ("change", "param_change", moved),
                            ("stat", "stat_change", None)):
        worst, where, median = _leaf_gaps(program[key], ref[key], keep)
        out[name + "_gap"] = (worst, where)
        out[name + "_median_gap"] = (median, "median leaf")
        if key != "stat_change":
            out[name + "_shrink"] = (_shrink(program[key], ref[key], products),
                                     "median product weight")
    return out


def judge(numbers, limits):
    """(correct, {number: {"value", "limit", "where"}}); a number with
    no limit is reported and not compared, one the limits name and the
    run lacks fails."""
    rows, ok = {}, True
    for name, (value, where) in numbers.items():
        limit = limits.get(name)
        rows[name] = {"value": value, "limit": limit, "where": where}
        if limit is not None and not value <= limit:
            ok = False
    for name in limits:
        if name not in numbers:
            rows[name] = {"value": None, "limit": limits[name],
                          "where": "not produced"}
            ok = False
    return ok, rows


# ---------------------------------------------------------- the control
def _scaled_round(t, dtype, top):
    """`t` rounded to the float8 `dtype` under one scale for the tensor
    (its largest magnitude lands on `top`), as an fp8 matrix unit
    would take it."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    return (t * scale).astype(dtype).astype(t.dtype) / scale


@jax.custom_vjp
def _fp8_operand(t):
    return _scaled_round(t, jnp.float8_e4m3fn, 448.0)


_fp8_operand.defvjp(lambda t: (_fp8_operand(t), None), lambda _r, g: (g,))


@jax.custom_vjp
def _fp8_result(t):
    return _scaled_round(t, jnp.float8_e4m3fn, 448.0)


_fp8_result.defvjp(
    lambda t: (_fp8_result(t), None),
    lambda _r, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


class Fp8:
    """The nearest precision below the bfloat16 the configurations
    state, put where they put bfloat16: every product takes its
    operands in float8 e4m3 and hands on its result in e4m3, each
    unit's sum is held in e4m3, and the gradients that come back through
    them are held in float8 e5m2, each tensor under one scale of its
    own.  Sums inside a product, BatchNorm's statistics and the update
    stay in float32, as the program's do."""
    operand = staticmethod(_fp8_operand)
    result = staticmethod(_fp8_result)


def _bf16_round(t):
    return t.astype(jnp.bfloat16).astype(t.dtype)


@jax.custom_vjp
def _bf16_operand(t):
    return _bf16_round(t)


_bf16_operand.defvjp(lambda t: (_bf16_round(t), None), lambda _r, g: (g,))


@jax.custom_vjp
def _bf16_result(t):
    return _bf16_round(t)


_bf16_result.defvjp(lambda t: (_bf16_round(t), None),
                    lambda _r, g: (_bf16_round(g),))


class Bf16:
    """The precision the configurations state, put on the reference: a
    second witness of what bfloat16 alone does to each number (operands,
    results and incoming gradients of every product rounded to it)."""
    operand = staticmethod(_bf16_operand)
    result = staticmethod(_bf16_result)
