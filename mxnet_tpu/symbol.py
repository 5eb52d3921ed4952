"""Symbol — declarative graph IR.

TPU-native replacement for nnvm Symbol + the C API symbolic layer
(python/mxnet/symbol.py, src/c_api/c_api_symbolic.cc). A Symbol is a list of
(node, out_index) heads over a DAG of ``_Node``s; composition, shape/type
inference and JSON save/load live here, and ``bind``/``simple_bind`` lower
the whole graph to one jitted XLA computation (executor.py) — the reference's
GraphExecutor + PlanMemory passes collapse into XLA compilation
(SURVEY.md §7).

JSON format follows the reference layout ({nodes, arg_nodes, heads}); attrs
are serialized as strings like nnvm does, and ``load`` accepts both the
"attrs" and legacy "param" keys (LoadLegacyJSON, c_api_symbolic.cc:330).
"""
from __future__ import annotations

import ast as _ast
import json
import sys

import numpy as onp

from .base import MXNetError
from .attribute import AttrScope
from .name import NameManager
from . import registry as _registry

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "pow", "maximum", "minimum"]


class _Node:
    """One graph node: an op application or a variable (op=None)."""

    __slots__ = ("op", "name", "attrs", "inputs", "is_aux", "_attr_dict",
                 "auto_named")

    def __init__(self, op, name, attrs=None, inputs=None, is_aux=False,
                 attr_dict=None, auto_named=False):
        self.op = op            # OpDef or None for variables
        self.name = name
        self.attrs = attrs or {}          # op parameters (typed)
        self.inputs = inputs or []        # list of (node, out_idx)
        self.is_aux = is_aux
        self._attr_dict = attr_dict or {}  # user attrs (ctx_group, ...)
        self.auto_named = auto_named  # name came from NameManager, not user

    def num_outputs(self):
        return 1 if self.op is None else self.op.num_outputs(self.attrs)


class Symbol:
    """Symbolic multi-output handle (python/mxnet/symbol.py Symbol)."""

    def __init__(self, heads):
        self._heads = list(heads)  # list of (node, out_idx)

    # ------------------------------------------------------------- graph
    def _topo(self):
        """Topological order of nodes reachable from heads (input-first DFS,
        matching nnvm's post-order used for list_arguments ordering)."""
        visited = set()
        order = []

        def visit(node):
            if id(node) in visited:
                return
            visited.add(id(node))
            for (src, _) in node.inputs:
                visit(src)
            order.append(node)

        for (n, _) in self._heads:
            visit(n)
        return order

    def list_arguments(self):
        return [n.name for n in self._topo() if n.op is None and not n.is_aux]

    def list_outputs(self):
        outs = []
        for (n, idx) in self._heads:
            if n.op is None:
                outs.append(n.name)
            else:
                onames = n.op.list_outputs(n.attrs)
                outs.append("%s_%s" % (n.name, onames[idx]))
        return outs

    def list_auxiliary_states(self):
        return [n.name for n in self._topo() if n.op is None and n.is_aux]

    @property
    def name(self):
        if len(self._heads) == 1:
            return self._heads[0][0].name
        return None

    def attr(self, key):
        if len(self._heads) == 1:
            return self._heads[0][0]._attr_dict.get(key, None)
        return None

    def attr_dict(self):
        ret = {}
        for n in self._topo():
            d = dict(n._attr_dict)
            if n.op is not None:
                d.update({k: str(v) for k, v in n.attrs.items()})
            if d:
                ret[n.name] = d
        return ret

    def _set_attr(self, **kwargs):
        for (n, _) in self._heads:
            n._attr_dict.update(kwargs)

    # ------------------------------------------------------ composition
    def __call__(self, *args, **kwargs):
        s = self.__copy__()
        s._compose(*args, **kwargs)
        return s

    def _compose(self, *args, **kwargs):
        """Substitute free variables with symbols (nnvm Symbol::Compose):
        kwargs match variable *names* anywhere in the graph; positional args
        match free variables in list_arguments order."""
        name = kwargs.pop("name", None)
        # "one head node" includes multi-output atomics (SliceChannel, RNN)
        # whose heads are N outputs of the SAME node
        single = len({id(n) for (n, _) in self._heads}) == 1
        head = self._heads[0][0] if single else None
        if kwargs and single and head.op is not None:
            # nnvm Compose on an ATOMIC head matches kwargs against the
            # op's argument names (data/weight/...). Our placeholders are
            # eager, so "atomic" = every input is still the placeholder
            # variable _create generated (named <head>_<arg>); once any
            # input was bound, the symbol is composite and kwargs match
            # variable names like everywhere else.
            argnames = head.op.list_arguments(head.attrs)
            pairs = list(zip(head.inputs, argnames))
            if all(src.op is None and src.auto_named
                   and src.name == head.name + "_" + nm
                   for (src, _), nm in pairs) and pairs:
                trans = {nm: src.name for (src, _), nm in pairs}
                kwargs = {trans.get(k, k): v for k, v in kwargs.items()}
        order = self._topo()
        free_vars = [n for n in order if n.op is None]
        repl = {}  # id(var node) -> (node, out_idx) replacement head
        # positional args bind in list_arguments order, which excludes aux
        # states (reference symbol.py __call__ / nnvm Symbol::Compose)
        pos_vars = [n for n in free_vars if not n.is_aux]
        if len(args) > len(pos_vars):
            raise MXNetError(
                "too many positional arguments: %d given, %d free variables"
                % (len(args), len(pos_vars)))
        for var, s in zip(pos_vars, args):
            repl[id(var)] = s._heads[0]
        by_name = {n.name: n for n in free_vars}
        for k, v in kwargs.items():
            if k not in by_name:
                raise MXNetError("cannot compose: no variable named %s" % k)
            repl[id(by_name[k])] = v._heads[0]
        for n in order:
            n.inputs = [repl.get(id(src), (src, oi))
                        for (src, oi) in n.inputs]
        self._heads = [repl.get(id(n), (n, oi)) for (n, oi) in self._heads]
        if name and single and head.op is not None:
            # nnvm Symbol::Compose assigns the node name BEFORE argument
            # names are synthesized (nnvm/src/core/symbolic.cc), so a
            # compose-time name flows into auto param names (fc1_weight).
            # Our placeholders are eager: rename the head's still-free
            # direct-input PLACEHOLDERS (auto_named vars _create made)
            # that carry its auto-generated prefix. User-chosen names —
            # even ones sharing the prefix — are never touched.
            old = head.name
            head.name = name
            if old != name and head.auto_named:
                for (src, _) in head.inputs:
                    if src.op is None and src.auto_named \
                            and src.name.startswith(old + "_"):
                        src.name = name + src.name[len(old):]
            head.auto_named = False

    def __copy__(self):
        # deep copy of reachable graph
        mapping = {}

        def copy_node(n):
            if id(n) in mapping:
                return mapping[id(n)]
            c = _Node(n.op, n.name, dict(n.attrs), [], n.is_aux,
                      dict(n._attr_dict), auto_named=n.auto_named)
            mapping[id(n)] = c
            c.inputs = [(copy_node(s), i) for (s, i) in n.inputs]
            return c

        return Symbol([(copy_node(n), i) for (n, i) in self._heads])

    def __getitem__(self, index):
        if isinstance(index, str):
            outs = self.list_outputs()
            for i, nm in enumerate(outs):
                if nm == index or nm == index + "_output":
                    return Symbol([self._heads[i]])
            raise ValueError("cannot find output %s" % index)
        return Symbol([self._heads[index]])

    def __iter__(self):
        return (self[i] for i in range(len(self._heads)))

    def __len__(self):
        return len(self._heads)

    def get_internals(self):
        """Symbol whose outputs are every node's outputs (symbol.py
        get_internals) — used for feature extraction / monitor."""
        heads = []
        for n in self._topo():
            for i in range(n.num_outputs()):
                heads.append((n, i))
        return Symbol(heads)

    def get_children(self):
        if len(self._heads) != 1:
            return None
        node = self._heads[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    # ------------------------------------------------------- operators
    def __add__(self, other):
        return _sym_binary(self, other, "_plus", "_plus_scalar")

    def __radd__(self, other):
        return self.__add__(other)

    def __sub__(self, other):
        return _sym_binary(self, other, "_minus", "_minus_scalar")

    def __rsub__(self, other):
        return _sym_binary(self, other, None, "_rminus_scalar")

    def __mul__(self, other):
        return _sym_binary(self, other, "_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self.__mul__(other)

    def __div__(self, other):
        return _sym_binary(self, other, "_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, other):
        return _sym_binary(self, other, None, "_rdiv_scalar")

    __rtruediv__ = __rdiv__

    def __pow__(self, other):
        return _sym_binary(self, other, "_power", "_power_scalar")

    def __neg__(self):
        return _sym_binary(self, -1.0, None, "_mul_scalar")

    def __eq__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return _sym_binary(self, other, "_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return _sym_binary(self, other, "_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, other):
        return _sym_binary(self, other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return _sym_binary(self, other, "_greater_equal",
                           "_greater_equal_scalar")

    def __lt__(self, other):
        return _sym_binary(self, other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _sym_binary(self, other, "_lesser_equal",
                           "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __repr__(self):
        name = self.name
        return "<Symbol %s>" % (name if name else "Grouped")

    # ------------------------------------------------------- inference
    def infer_shape(self, *args, **kwargs):
        arg_shapes, out_shapes, aux_shapes = self.infer_shape_partial(
            *args, **kwargs)
        if arg_shapes is not None and any(s is None for s in arg_shapes):
            unknown = [n for n, s in zip(self.list_arguments(), arg_shapes)
                       if s is None]
            raise MXNetError("cannot infer shapes for arguments: %s"
                             % unknown)
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_partial(self, *args, **kwargs):
        """Bidirectional shape inference over the graph (nnvm InferShape
        pass, graph_executor.cc:425). Iterates node-local infer_shape to a
        fixpoint so layer ops can fill parameter shapes from data shapes."""
        arg_names = self.list_arguments()
        known = {}
        if args:
            for nm, s in zip(arg_names, args):
                if s is not None:
                    known[nm] = tuple(s)
        valid = set(arg_names) | set(self.list_auxiliary_states())
        for k, v in kwargs.items():
            if k not in valid:
                raise ValueError(
                    "Unknown argument %s in infer_shape (arguments: %s)"
                    % (k, arg_names))
            if v is not None:
                known[k] = tuple(v)

        order = self._topo()
        shapes = {}  # id(node) -> list of out shapes (or None)
        for n in order:
            if n.op is None:
                s = known.get(n.name)
                if s is None and "__shape__" in n._attr_dict:
                    # Variable(shape=...) hint seeds inference, matching
                    # reference python/mxnet/symbol.py Variable semantics
                    s = tuple(_ast.literal_eval(n._attr_dict["__shape__"]))
                shapes[id(n)] = [s]
            else:
                shapes[id(n)] = [None] * n.num_outputs()

        for _ in range(3):  # fixpoint iterations
            changed = False
            for n in order:
                if n.op is None:
                    cur = shapes[id(n)][0]
                    if cur is None and n.name in known:
                        shapes[id(n)][0] = known[n.name]
                        changed = True
                    continue
                in_sh = [shapes[id(s)][oi] for (s, oi) in n.inputs]
                n_args = len(n.op.list_arguments(n.attrs))
                main_in = in_sh[:n_args]
                aux_in = in_sh[n_args:]
                try:
                    filled, outs, aux_filled = n.op.infer_shape(
                        n.attrs, main_in, aux_in)
                except Exception:
                    continue
                for (src, oi), s in zip(n.inputs,
                                        (filled or []) + (aux_filled or [])):
                    if s is not None and shapes[id(src)][oi] is None:
                        shapes[id(src)][oi] = tuple(s)
                        changed = True
                if outs is not None:
                    for i, s in enumerate(outs):
                        if s is not None and shapes[id(n)][i] is None:
                            shapes[id(n)][i] = tuple(s)
                            changed = True
            if not changed:
                break

        arg_shapes = [shapes[id(n)][0] for n in order
                      if n.op is None and not n.is_aux]
        aux_shapes = [shapes[id(n)][0] for n in order
                      if n.op is None and n.is_aux]
        out_shapes = [shapes[id(n)][oi] for (n, oi) in self._heads]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Type inference: default float32 everywhere unless specified
        (the reference infers through FInferType; dtype mixing is rare)."""
        arg_names = self.list_arguments()
        known = {}
        for nm, t in zip(arg_names, args):
            if t is not None:
                known[nm] = onp.dtype(t)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = onp.dtype(v)
        default = onp.dtype(onp.float32)
        if known:
            default = next(iter(known.values()))
        arg_types = [known.get(n, default) for n in arg_names]
        out_types = [default] * len(self._heads)
        aux_types = [default] * len(self.list_auxiliary_states())
        return arg_types, out_types, aux_types

    # -------------------------------------------------------- serialize
    def tojson(self):
        order = self._topo()
        idx = {id(n): i for i, n in enumerate(order)}
        nodes = []
        for n in order:
            entry = {
                "op": "null" if n.op is None else n.op.name,
                "name": n.name,
                "inputs": [[idx[id(s)], oi] for (s, oi) in n.inputs],
            }
            attrs = {k: str(v) for k, v in n.attrs.items()}
            if attrs:
                entry["attrs"] = attrs
            if n._attr_dict:
                entry["attr"] = dict(n._attr_dict)
            if n.is_aux:
                entry["__aux__"] = True
            nodes.append(entry)
        return json.dumps({
            "nodes": nodes,
            "arg_nodes": [i for i, n in enumerate(order) if n.op is None],
            "heads": [[idx[id(n)], oi] for (n, oi) in self._heads],
            "attrs": {"mxnet_version": ["int", 905]},
        }, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ----------------------------------------------------------- binding
    def simple_bind(self, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, **kwargs):
        """Allocate all arguments from inferred shapes then bind
        (python/mxnet/symbol.py:988-1068)."""
        from . import ndarray as nd

        arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
        arg_types, _, aux_types = self.infer_type(
            **{k: v for k, v in (type_dict or {}).items()})
        args = [nd.zeros(s, ctx=ctx, dtype=t)
                for s, t in zip(arg_shapes, arg_types)]
        aux = [nd.zeros(s, ctx=ctx, dtype=t)
               for s, t in zip(aux_shapes, aux_types)]
        if grad_req != "null":
            reqs = grad_req
            if isinstance(grad_req, str):
                reqs = {n: grad_req for n in self.list_arguments()}
            elif isinstance(grad_req, list):
                reqs = dict(zip(self.list_arguments(), grad_req))
            args_grad = {n: nd.zeros(s, ctx=ctx, dtype=t)
                         for n, s, t in zip(self.list_arguments(), arg_shapes,
                                            arg_types)
                         if reqs.get(n, "null") != "null"}
        else:
            args_grad = None
        return self.bind(ctx, args, args_grad=args_grad, grad_req=grad_req,
                         aux_states=aux, group2ctx=group2ctx)

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from .executor import Executor
        return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    # ------------------------------------------------------------ eval
    def eval(self, ctx=None, **kwargs):
        from .context import cpu
        ctx = ctx or cpu()
        ex = self.bind(ctx, kwargs, grad_req="null")
        return ex.forward()


# ---------------------------------------------------------------------------
# construction helpers
# ---------------------------------------------------------------------------
def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, **kwargs):
    """Create a symbolic variable (mx.sym.Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    attr = AttrScope.current().get(attr)
    attr = dict(attr) if attr else {}
    if shape is not None:
        attr["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        attr["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        attr["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        attr["__dtype__"] = str(onp.dtype(dtype))
    if init is not None:
        attr["__init__"] = init.dumps() if hasattr(init, "dumps") else str(init)
    node = _Node(None, name, attr_dict=attr)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols):
    """Group symbols into one multi-output symbol (mx.sym.Group)."""
    heads = []
    for s in symbols:
        heads.extend(s._heads)
    return Symbol(heads)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


def load_json(json_str):
    """Load a symbol from JSON; tolerates the legacy "param" attr key
    (LoadLegacyJSON upgrade path, c_api_symbolic.cc:330)."""
    data = json.loads(json_str)
    raw_nodes = data["nodes"]
    nodes = []
    for e in raw_nodes:
        op_name = e.get("op", "null")
        attrs = e.get("attrs", e.get("param", {})) or {}
        user_attr = e.get("attr", {}) or {}
        if op_name == "null":
            n = _Node(None, e["name"], attr_dict=dict(user_attr),
                      is_aux=bool(e.get("__aux__", False)))
        else:
            op = _registry.get_op(op_name)
            typed = _registry.parse_attrs(op, attrs)
            n = _Node(op, e["name"], typed, attr_dict=dict(user_attr))
        nodes.append(n)
    for n, e in zip(nodes, raw_nodes):
        n.inputs = [(nodes[i], oi) for (i, oi, *_rest) in
                    [tuple(x) for x in e.get("inputs", [])]]
        # mark aux variables by position (inputs beyond the arg list)
        if n.op is not None:
            n_args = len(n.op.list_arguments(n.attrs))
            for (src, _) in n.inputs[n_args:]:
                if src.op is None:
                    src.is_aux = True
    heads = [(nodes[h[0]], h[1]) for h in data["heads"]]
    return Symbol(heads)


def fromjson(json_str):
    return load_json(json_str)


# ---------------------------------------------------------------------------
# symbol op wrappers (auto-generated from the registry, mirroring
# _init_symbol_module in python/mxnet/symbol.py)
# ---------------------------------------------------------------------------
def _sym_binary(lhs, rhs, op_name, scalar_op_name):
    if isinstance(rhs, Symbol):
        if op_name is None:
            raise MXNetError("unsupported symbol operation")
        return _create(op_name, [lhs, rhs], {})
    if isinstance(rhs, (int, float)):
        return _create(scalar_op_name, [lhs], {"scalar": float(rhs)})
    raise TypeError("type %s not supported" % str(type(rhs)))


def _create(op_name, input_syms, attrs, name=None, named_inputs=None):
    op = _registry.get_op(op_name)
    hint = op.name.lower().lstrip("_")
    auto_named = name is None
    name = NameManager.current().get(name, hint)
    user_attrs = AttrScope.current().get(None)

    # dmlc::Parameter parity: attribute values may arrive as their wire
    # strings ("(3,3)", "8", "True") — the reference stringifies every
    # param and re-parses by declared type, so kernel="(3,3)" is as
    # valid as kernel=(3,3).  The C API symbol path (and any frontend
    # binding) depends on this coercion.
    attrs = _registry.parse_attrs(op, attrs)

    if op.variable_args is not None and op.variable_args not in attrs:
        attrs[op.variable_args] = len(input_syms)

    arg_names = op.list_arguments(attrs)
    aux_names = op.list_auxiliary_states(attrs)
    named_inputs = named_inputs or {}
    inputs = []
    pos = list(input_syms)
    for nm in arg_names:
        if nm in named_inputs:
            inputs.append(named_inputs[nm]._heads[0])
        elif pos:
            inputs.append(pos.pop(0)._heads[0])
        else:
            vnode = _Node(None, "%s_%s" % (name, nm),
                          attr_dict=dict(user_attrs) if user_attrs else {},
                          auto_named=True)
            inputs.append((vnode, 0))
    if pos:
        # surplus positional inputs must error, not vanish — e.g.
        # SequenceMask(x, l) without use_sequence_length=True takes only
        # (data,); the reference's compose rejects surplus args too
        raise MXNetError(
            "%s takes %d input(s) %s for these attributes; %d extra "
            "positional input(s) given" % (op.name, len(arg_names),
                                           arg_names, len(pos)))
    unknown = [k for k in named_inputs
               if k not in arg_names and k not in aux_names]
    if unknown:
        raise MXNetError(
            "%s got unexpected input(s) %s (arguments for these "
            "attributes: %s)" % (op.name, unknown, arg_names))
    # aux states appended after args, auto-created (BatchNorm moving stats)
    for nm in aux_names:
        if nm in named_inputs:
            head = named_inputs[nm]._heads[0]
            head[0].is_aux = True
            inputs.append(head)
        else:
            vnode = _Node(None, "%s_%s" % (name, nm), is_aux=True,
                          auto_named=True)
            inputs.append((vnode, 0))

    node = _Node(op, name, attrs, inputs,
                 attr_dict=dict(user_attrs) if user_attrs else {},
                 auto_named=auto_named)
    n_out = node.num_outputs()
    return Symbol([(node, i) for i in range(n_out)])


def _make_sym_func(op):
    def fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        named_inputs = {k: v for k, v in kwargs.items()
                        if isinstance(v, Symbol)}
        # None kwargs mean "default" — dropped before they reach node
        # attrs (same contract as the ndarray wrapper, ndarray.py)
        attrs = {k: v for k, v in kwargs.items()
                 if v is not None and not isinstance(v, Symbol)}
        input_syms = [a for a in args if isinstance(a, Symbol)]
        s = _create(op.name, input_syms, attrs, name=name,
                    named_inputs=named_inputs)
        if attr:
            s._set_attr(**attr)
        return s

    fn.__name__ = op.name
    fn.__doc__ = (op.fcompute.__doc__ or "") + "\n\n(symbol op: %s)" % op.name
    return fn


def _init_symbol_module():
    mod = sys.modules[__name__]
    for name in _registry.list_ops():
        if hasattr(mod, name):  # don't shadow module helpers (load, pow, ...)
            continue
        op = _registry.get_op(name)
        setattr(mod, name, _make_sym_func(op))


def pow(base, exp):
    return base ** exp


def maximum(lhs, rhs):
    if isinstance(lhs, Symbol) and isinstance(rhs, Symbol):
        return _create("_maximum", [lhs, rhs], {})
    s, other = (lhs, rhs) if isinstance(rhs, (int, float)) else (rhs, lhs)
    return _create("_maximum_scalar", [s], {"scalar": float(other)})


def minimum(lhs, rhs):
    if isinstance(lhs, Symbol) and isinstance(rhs, Symbol):
        return _create("_minimum", [lhs, rhs], {})
    s, other = (lhs, rhs) if isinstance(rhs, (int, float)) else (rhs, lhs)
    return _create("_minimum_scalar", [s], {"scalar": float(other)})


from . import ops as _ops  # noqa: E402,F401
_init_symbol_module()
