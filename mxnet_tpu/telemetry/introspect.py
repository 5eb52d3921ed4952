"""Program introspection — every compiled XLA program in the process
and XLA's own account of its FLOPs and bytes.

* :func:`analyze_compiled` — THE one cost/memory-analysis helper: a jax
  ``Compiled`` in, ``{"flops", "bytes_accessed", "temp_bytes", ...}``
  out (example/memcost, tools/bn_pallas_probe and
  tools/hlo_byte_audit ride it).
* :class:`ProgramInventory` — every jitted program the stack runs (fit
  step, grouped scan, optimizer update, padded eval, each serving
  bucket) registers its jit handle + aval skeleton at first launch
  (``MeshExecutorGroup._note_program`` / ``Updater._update_group``).
  Registration is one dict write; the expensive analysis is LAZY and
  re-acquires the ``Compiled`` through the jit trace cache — it never
  re-executes user code on the step path, and it runs under
  :meth:`CompileWatch.suppressed` so the zero-post-warmup-retraces
  contract holds with introspection live. Analyzed numbers publish as a
  ``programs.*`` gauge scope and as a JSON report
  (:meth:`dump_programs` / ``telemetry.dump_programs``).

The program publishes counts and bytes, never a share of a peak: the
one peak table and the only utilisation figures are the benchmark's
(``benchmark/peaks.py``, ``step_mfu_pct``, ``train_step_roofline``),
over a traced device time.

Scaling note: ``cost_analysis()`` reports the PER-DEVICE partitioned
module; inventory entries scale by the mesh size (``n_dev``) so totals
are whole-mesh.
"""
from __future__ import annotations

import collections
import threading
import time

__all__ = ["analyze_compiled", "aval_skeleton", "ProgramInventory"]


def aval_skeleton(args):
    """The aval skeleton of a call's argument tree — every array leaf
    replaced by a ``ShapeDtypeStruct`` — THE one rule every inventory
    registration site uses, so ``fn.lower(*avals)`` re-acquisition
    stays consistent with how the skeletons were taken (and a future
    change — preserving shardings, weak_type — lands in one place)."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, args)


def analyze_compiled(compiled):
    """XLA's own account of a jax ``Compiled``: cost analysis (true
    flops / bytes accessed) + memory analysis (temp / argument / output
    / donated-alias buffer bytes), as one flat dict.

    This is THE shared cost/memory-analysis helper — example/memcost,
    tools/bn_pallas_probe and tools/hlo_byte_audit all ride it (their
    recorded field names are their own; the extraction rule lives here
    once). Values are PER-DEVICE for partitioned modules (scale by
    mesh size for whole-mesh totals)."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else (ca or {})
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - memory stats are backend-optional
        ma = None
    if ma is not None:
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        out["argument_bytes"] = int(
            getattr(ma, "argument_size_in_bytes", 0))
        out["output_bytes"] = int(getattr(ma, "output_size_in_bytes", 0))
        out["alias_bytes"] = int(getattr(ma, "alias_size_in_bytes", 0))
        out["generated_code_bytes"] = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
    return out


class ProgramInventory(object):
    """Registry of every compiled XLA program in the process
    (module docstring). Entries are either jit handles (analysis lazy,
    through the trace cache) or analytic accounts (e.g. the optimizer
    update folded into the fused train step)."""

    def __init__(self, registry=None, capacity=256):
        self._lock = threading.Lock()
        self._entries = collections.OrderedDict()
        self._capacity = int(capacity)
        self._registry = registry

    def _scope(self):
        if self._registry is None:
            import mxnet_tpu.telemetry as _tel
            self._registry = _tel.registry()
        return self._registry

    # -- registration ---------------------------------------------------
    def register(self, name, fn=None, args_avals=None, kind="",
                 n_dev=1, device_kind="", meta=None, flops=None,
                 bytes_accessed=None):
        """Register (or replace) one program entry.

        ``fn`` + ``args_avals``: a jit function and the aval skeleton of
        a call that already traced — analysis later re-acquires the
        ``Compiled`` via ``fn.lower(*avals).compile()`` (a trace-cache
        hit, never a user-code re-execution; see
        ``MeshExecutorGroup._note_program``). ``fn=None`` registers an
        ANALYTIC entry from explicit per-device ``flops`` /
        ``bytes_accessed`` (the separate-optimizer accounting).
        Registration is cheap and unconditional; nothing is analyzed
        until asked. Returns the entry name."""
        entry = {
            "name": str(name), "kind": str(kind), "n_dev": int(n_dev),
            "device_kind": str(device_kind), "meta": dict(meta or {}),
            "registered_ts": time.time(),
            "fn": fn, "avals": args_avals,
            "analytic": fn is None,
            "analysis": None,
        }
        if fn is None:
            entry["analysis"] = {
                "flops": float(flops or 0.0),
                "bytes_accessed": float(bytes_accessed or 0.0),
            }
        with self._lock:
            self._entries.pop(entry["name"], None)
            self._entries[entry["name"]] = entry
            while len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
        return entry["name"]

    def names(self):
        with self._lock:
            return list(self._entries)

    def clear(self):
        """Drop every entry — test isolation (a process-global
        inventory otherwise carries programs registered by earlier
        suites, whose lazy analysis can dominate an unrelated
        ``dump_programs``/``GET /programs``)."""
        with self._lock:
            self._entries.clear()

    def __len__(self):
        with self._lock:
            return len(self._entries)

    # -- analysis -------------------------------------------------------
    def analyze(self, name, refresh=False):
        """The analyzed report dict for one entry (None for unknown
        names). First call on a handle entry lowers+compiles through
        the jit caches under :meth:`CompileWatch.suppressed` — an
        analysis pass must never count as (or warn about) a steady-
        state retrace — then caches; flops/bytes are n_dev-scaled
        totals, with per-device values alongside. Failures land in the
        entry as ``{"error": ...}`` rather than raising (introspection
        is diagnostics, not control flow)."""
        with self._lock:
            entry = self._entries.get(str(name))
        if entry is None:
            return None
        if entry["analysis"] is None or refresh:
            import mxnet_tpu.telemetry as _tel
            try:
                with _tel.compile_watch().suppressed():
                    avals = entry["avals"] or ()
                    comp = entry["fn"].lower(*avals).compile()
                entry["analysis"] = analyze_compiled(comp)
            except Exception as e:  # noqa: BLE001 - best-effort diagnostics
                entry["analysis"] = {"error": str(e)[:200]}
        return self._render(entry)

    def _render(self, entry):
        a = entry["analysis"] or {}
        out = {"name": entry["name"], "kind": entry["kind"],
               "n_dev": entry["n_dev"],
               "device_kind": entry["device_kind"],
               "analytic": entry["analytic"], "meta": dict(entry["meta"])}
        if "error" in a:
            out["error"] = a["error"]
            return out
        n_dev = max(entry["n_dev"], 1)
        out["flops_per_device"] = a.get("flops", 0.0)
        out["bytes_per_device"] = a.get("bytes_accessed", 0.0)
        out["flops"] = a.get("flops", 0.0) * n_dev
        out["bytes_accessed"] = a.get("bytes_accessed", 0.0) * n_dev
        for k in ("temp_bytes", "argument_bytes", "output_bytes",
                  "alias_bytes", "generated_code_bytes"):
            if k in a:
                out[k] = a[k]
        out["donated"] = a.get("alias_bytes", 0) > 0
        if entry["avals"] is not None:
            try:
                import jax
                out["n_args"] = len(
                    jax.tree_util.tree_leaves(entry["avals"]))
            except Exception:  # noqa: BLE001
                pass
        self._publish(out)
        return out

    def _publish(self, report):
        """Mirror one analyzed entry into the ``programs.*`` gauge
        scope (Prometheus/JSONL-visible)."""
        try:
            scope = self._scope().scope("programs.%s" % report["name"])
            scope.gauge("flops").set(report.get("flops", 0.0))
            scope.gauge("bytes_accessed").set(
                report.get("bytes_accessed", 0.0))
            if "temp_bytes" in report:
                scope.gauge("temp_bytes").set(report["temp_bytes"])
        except Exception:  # noqa: BLE001 - publishing is best-effort
            pass

    def report(self):
        """Every entry analyzed (lazy passes run now), sorted by name."""
        return [self.analyze(n) for n in sorted(self.names())]

    def dump_programs(self, path=None):
        """The full inventory as a JSON report; ``path=`` also writes
        it (tmp+rename, so a reader never sees a torn file). Returns
        the report dict."""
        report = {
            "format": "program-inventory-r1",
            "generated_ts": round(time.time(), 3),
            "n_programs": len(self),
            "programs": self.report(),
        }
        if path is not None:
            from .export import atomic_json_dump
            atomic_json_dump(path, report)
        return report
