"""Generate docs/api/ops.md from the operator registry's typed-param
reflection — the same metadata MXSymbolGetAtomicSymbolInfo serves over
the C API (the reference generates its op docs from dmlc::Parameter
reflection the same way; docs/api in the reference tree).

Run:  python docs/gen_api_ref.py
Test: tests/test_docs.py regenerates and diffs against the committed
file, so the reference can never go stale.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def op_section(name, op):
    lines = ["### %s" % name]
    doc = (op.fcompute.__doc__ or "").strip()
    if doc:
        # full docstring, re-wrapped: the op docstrings carry the
        # reference citations and semantic contracts — the reference's
        # generated op docs ship the full dmlc::Parameter description
        # the same way
        import textwrap
        first, _, rest = doc.partition("\n")
        body = first.strip()
        if rest.strip():
            body += "\n" + textwrap.dedent(rest).rstrip()
        lines.append("")
        lines.append(body)
    if op.alias:
        lines.append("")
        lines.append("*Aliases:* " + ", ".join("`%s`" % a for a in op.alias))
    # arguments (may be attr-dependent)
    if op.variable_args is not None:
        args = "variable (`%s` inputs, named `arg0..`)" % op.variable_args
    elif callable(op.arg_names):
        try:
            args = ", ".join("`%s`" % a for a in op.arg_names({}))
            args += " *(list depends on attributes)*"
        except Exception:
            args = "*attribute-dependent*"
    else:
        args = ", ".join("`%s`" % a for a in op.arg_names) or "*(none)*"
    lines.append("")
    lines.append("*Inputs:* " + args)
    if callable(op.aux_names):
        lines.append("")
        lines.append("*Auxiliary states:* *attribute-dependent*")
    elif op.aux_names:
        lines.append("")
        lines.append("*Auxiliary states:* "
                     + ", ".join("`%s`" % a for a in op.aux_names))
    n_out = op._num_outputs
    outs = ("attribute-dependent" if callable(n_out) else
            ", ".join("`%s`" % o for o in op.out_names))
    lines.append("")
    lines.append("*Outputs:* " + outs)
    if op.attr_types:
        lines.append("")
        lines.append("| attribute | type | required |")
        lines.append("|---|---|---|")
        for k in sorted(op.attr_types):
            t = op.attr_types[k]
            tname = getattr(t, "__name__", str(t))
            req = "yes" if k in op.required_attrs else ""
            lines.append("| `%s` | %s | %s |" % (k, tname, req))
    if op.needs_rng:
        lines.append("")
        lines.append("*Stochastic:* draws from the per-executor PRNG "
                     "key chain.")
    return "\n".join(lines)


def generate():
    from mxnet_tpu import registry

    names = sorted(n for n in registry.list_ops())
    seen = set()
    sections = []
    for n in names:
        op = registry.get_op(n)
        if id(op) in seen:
            continue  # aliases share one OpDef; document once
        seen.add(id(op))
        sections.append(op_section(op.name, op))
    header = (
        "# Operator API reference\n\n"
        "Generated by `docs/gen_api_ref.py` from the operator registry's\n"
        "typed-attribute reflection (the metadata behind\n"
        "`MXSymbolGetAtomicSymbolInfo`). Every operator is callable as\n"
        "`mx.sym.<Name>(...)` (symbolic) and, where it takes plain tensor\n"
        "inputs, as `mx.nd.<name>(...)` (imperative). %d registered\n"
        "names, %d distinct operators (the rest are aliases).\n\n"
        % (len(names), len(sections)))
    return header + "\n\n".join(sections) + "\n"


if __name__ == "__main__":
    out_path = os.path.join(ROOT, "docs", "api", "ops.md")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    text = generate()
    with open(out_path, "w") as f:
        f.write(text)
    print("wrote %s (%d lines)" % (out_path, text.count("\n")))
