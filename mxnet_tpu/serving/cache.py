"""Persistent serving compile cache — replica warm start as a
deserialize, not a recompile.

A new serving replica today cold-starts by compiling the entire bucket
ladder from scratch: on the bs128 ResNet-50 operating point that is
tens of seconds of XLA work per process before the first request is
served, which makes elastic autoscale against the ``slo.*`` burn-rate
gauges useless in practice. This module removes that wall in two
layers:

* **process-wide jax compilation cache** —
  :func:`enable_persistent_compile_cache`, called by entry points
  (``chip_smoke.py``, the example trainers) before their
  first compile, makes sure jax's own persistent compilation cache has
  a directory: ``JAX_COMPILATION_CACHE_DIR`` when the caller's
  environment sets it (jax reads that itself), else the fixed
  ``<checkout>/.jax_cache``. EVERY jit in the process — train step,
  augment program, serving buckets — then reuses compiled artifacts
  across processes.
* **explicit AOT executable cache** — ``Predictor.warmup(cache_dir=)``
  serializes each bucket's compiled program via
  ``jax.experimental.serialize_executable`` into an atomic,
  crc-verified :class:`ExecutableCache` entry. A second replica
  warming from the same directory deserializes every bucket and
  performs **zero** XLA compiles (CompileWatch-pinned), with served
  rows bitwise equal to the cold-start replica.

The cache key is the contract. An entry is keyed by

* ``params_digest`` — sha256 of the symbol JSON + every parameter's
  name/shape/dtype (:func:`mxnet_tpu.checkpoint.params_digest`, the
  SAME rule checkpoint manifests record), so an architecture drift
  refuses the entry while two checkpoints of one architecture share
  executables (parameter VALUES are runtime inputs);
* ``precision_mode`` — the resolved policy name; an executable built
  under ``int8_act``'s input quantization served under ``f32`` would
  be silent garbage, exactly the failure mode the keying must make
  impossible;
* ``bucket`` + ``input_sig`` — the padded batch size and the input
  row shapes/dtypes the program was specialized to;
* ``backend_sig`` — platform, device kind, device count, mesh axes,
  and the jax/jaxlib versions; executables are not portable across
  any of those.

Every mismatch path — drifted digest, wrong mode, different backend,
truncated or bit-flipped entry, a crashed ``.tmp-*`` partial — falls
back LOUDLY to a fresh compile (warning naming the drifted field); a
stale executable is never served silently. Entries commit with the
checkpoint subsystem's atomic idiom: write to a ``.tmp-*`` sibling,
fsync, ``os.replace`` — a ``.tmp-*`` file is structurally never
loadable.
"""
from __future__ import annotations

import glob
import json
import logging
import os
import pickle
import uuid
import zlib

__all__ = ["CacheMiss", "ExecutableCache", "cache_key",
           "enable_persistent_compile_cache", "backend_signature"]

_MAGIC = b"MXTPUEXEC1\n"
_FORMAT = 1
_TMP_PREFIX = ".tmp-"
_SUFFIX = ".mxexec"

logger = logging.getLogger("mxnet_tpu.serving")

# <checkout>/.jax_cache (git-ignored): where jax's persistent compile
# cache goes when JAX_COMPILATION_CACHE_DIR does not place it
_DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# key fields that must match field-by-field for an entry to load; the
# order is the order mismatch warnings report them in
KEY_FIELDS = ("params_digest", "precision_mode", "bucket", "input_sig",
              "backend_sig")


def enable_persistent_compile_cache():
    """Give jax's process-wide persistent compilation cache a directory
    and return it. Entry points call this before their first compile;
    package import never does (the CPU test suite would otherwise fill
    a cache that travels with the tree to a host with another CPU).

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing: jax reads
    that variable itself, and no code here may override a directory
    given from outside. Unset, jax is pointed at the fixed
    ``<checkout>/.jax_cache`` — a directory that moves never hits, so
    the path is never derived from a temporary name, a pid or the
    time."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    import jax
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_JAX_CACHE)
    return _DEFAULT_JAX_CACHE


def backend_signature(mesh_axes=None, n_dev=1, device_kind=None,
                      platform=None):
    """The executable-portability boundary as one stable string:
    platform, device kind, device count, mesh layout, jax + jaxlib
    versions. Two processes agreeing on this string may exchange
    serialized executables; any component drift refuses the entry."""
    import jax
    import jaxlib
    if platform is None:
        platform = jax.default_backend()
    parts = [
        "platform=%s" % platform,
        "device_kind=%s" % (device_kind or ""),
        "n_dev=%d" % int(n_dev),
        "mesh=%s" % json.dumps(dict(mesh_axes or {}), sort_keys=True),
        "jax=%s" % jax.__version__,
        "jaxlib=%s" % getattr(jaxlib, "__version__", "?"),
    ]
    return ";".join(parts)


def cache_key(params_digest, precision_mode, bucket, input_sig,
              backend_sig):
    """The full entry key as a plain dict (KEY_FIELDS order)."""
    return {
        "params_digest": str(params_digest),
        "precision_mode": str(precision_mode),
        "bucket": int(bucket),
        "input_sig": str(input_sig),
        "backend_sig": str(backend_sig),
    }


def input_signature(data_descs):
    """Canonical string of the input ROW shapes the bucket programs
    are specialized to (batch dim excluded — that is the bucket)."""
    return ";".join("%s:%s" % (name, tuple(shape[1:]))
                    for name, shape in sorted(data_descs))


class CacheMiss(Exception):
    """An entry could not be loaded. ``reason`` is one of ``absent``
    (first run — informational), ``key-mismatch`` (an entry exists for
    this bucket but was built under a different key — loud), or
    ``corrupt`` (truncated / bit-flipped / unreadable — loud)."""

    def __init__(self, reason, detail=""):
        self.reason = reason
        self.detail = detail
        super().__init__("%s%s" % (reason, (": " + detail) if detail
                                   else ""))


def _entry_name(key):
    """Filename for a key: every key field participates (digest/mode
    spelled for humans, the full key hashed in), so a different key can
    never resolve to the same file — correctness by construction; the
    header check below is defense in depth."""
    import hashlib
    full = hashlib.sha256(
        "|".join(str(key[f]) for f in KEY_FIELDS)
        .encode("utf-8")).hexdigest()[:16]
    mode = "".join(c if c.isalnum() else "_"
                   for c in key["precision_mode"])[:24]
    return "%s-%s-b%d-%s%s" % (key["params_digest"][:12], mode,
                               key["bucket"], full, _SUFFIX)


class ExecutableCache(object):
    """Directory of atomic, crc-verified serialized-executable entries.

    One entry = one ``(payload, in_tree, out_tree)`` trio from
    ``jax.experimental.serialize_executable.serialize``, framed as::

        MXTPUEXEC1\\n
        <json header line: format, key fields, payload size, crc32>\\n
        <pickled payload bytes>

    Commit is atomic (``.tmp-*`` sibling + fsync + ``os.replace``, the
    checkpoint subsystem's idiom); readers only ever open the exact
    final name, so a crashed partial is invisible — ``.tmp-*`` is never
    loadable, structurally and by the explicit guard in :meth:`load`.
    """

    def __init__(self, directory):
        self.directory = os.path.abspath(str(directory))
        os.makedirs(self.directory, exist_ok=True)

    # -- paths ----------------------------------------------------------
    def path_for(self, key):
        return os.path.join(self.directory, _entry_name(key))

    def entries(self):
        """Committed entry filenames (``.tmp-*`` partials excluded)."""
        return sorted(
            os.path.basename(p)
            for p in glob.glob(os.path.join(self.directory,
                                            "*" + _SUFFIX))
            if not os.path.basename(p).startswith(_TMP_PREFIX))

    def sweep_partials(self):
        """Remove crashed ``.tmp-*`` partials (writer-side hygiene)."""
        for p in glob.glob(os.path.join(self.directory,
                                        _TMP_PREFIX + "*")):
            try:
                os.remove(p)
            except OSError:
                pass

    # -- store ----------------------------------------------------------
    def store(self, key, payload, in_tree, out_tree):
        """Commit one entry atomically; returns its path. The pickled
        blob carries the serialized executable plus its arg/result
        treedefs (both picklable in jax>=0.4)."""
        from ..checkpoint.serialize import fsync_dir
        blob = pickle.dumps((payload, in_tree, out_tree),
                            protocol=pickle.HIGHEST_PROTOCOL)
        header = dict(key)
        header["format"] = _FORMAT
        header["size"] = len(blob)
        header["crc32"] = zlib.crc32(blob) & 0xFFFFFFFF
        final = self.path_for(key)
        tmp = os.path.join(self.directory, "%s%s-%s" % (
            _TMP_PREFIX, os.path.basename(final), uuid.uuid4().hex[:8]))
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(json.dumps(header, sort_keys=True).encode("utf-8"))
            f.write(b"\n")
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
        fsync_dir(self.directory)
        from .. import faults as _faults
        if _faults.armed():
            # poisoned-entry seam: corrupt the COMMITTED entry (a
            # storage fault after a clean commit) — the next replica's
            # load must refuse it loudly (CacheMiss "corrupt") and
            # fall back to a fresh compile, never serve stale bytes
            _faults.corrupt_file("serving.cache", self.directory,
                                 pattern=os.path.basename(final),
                                 bucket=key["bucket"])
        return final

    # -- load -----------------------------------------------------------
    def load(self, key):
        """Load and verify one entry -> ``(payload, in_tree,
        out_tree)``. Raises :class:`CacheMiss` on any failure —
        ``key-mismatch`` names the drifted field(s) when an entry for
        this bucket exists under a different key, so the fallback
        compile is loud about WHY."""
        path = self.path_for(key)
        name = os.path.basename(path)
        if name.startswith(_TMP_PREFIX):   # structural; belt and braces
            raise CacheMiss("corrupt", "refusing .tmp-* partial %s"
                            % name)
        if not os.path.exists(path):
            drift = self._describe_drift(key)
            if drift:
                raise CacheMiss("key-mismatch", drift)
            raise CacheMiss("absent", name)
        try:
            with open(path, "rb") as f:
                magic = f.read(len(_MAGIC))
                if magic != _MAGIC:
                    raise CacheMiss("corrupt", "%s: bad magic" % name)
                header = json.loads(f.readline().decode("utf-8"))
                blob = f.read()
        except CacheMiss:
            raise
        except Exception as e:  # noqa: BLE001 - any read/parse failure
            raise CacheMiss("corrupt", "%s: %s" % (name, e)) from e
        if header.get("format") != _FORMAT:
            raise CacheMiss("corrupt", "%s: format %r" % (
                name, header.get("format")))
        bad = [f for f in KEY_FIELDS if header.get(f) != key[f]]
        if bad:
            raise CacheMiss("key-mismatch", "%s: header disagrees on %s"
                            % (name, ", ".join(bad)))
        if len(blob) != header.get("size"):
            raise CacheMiss("corrupt", "%s: truncated (%d of %s bytes)"
                            % (name, len(blob), header.get("size")))
        if (zlib.crc32(blob) & 0xFFFFFFFF) != header.get("crc32"):
            raise CacheMiss("corrupt", "%s: crc32 mismatch" % name)
        try:
            payload, in_tree, out_tree = pickle.loads(blob)
        except Exception as e:  # noqa: BLE001 - any unpickle failure
            raise CacheMiss("corrupt", "%s: unpickle: %s"
                            % (name, e)) from e
        return payload, in_tree, out_tree

    def _describe_drift(self, key):
        """When the exact entry is absent but OTHER entries exist for
        this bucket, say which key fields drifted (the loud half of the
        fallback). Returns "" when the directory simply has no entry
        for the bucket (a plain first-run miss)."""
        want_b = "-b%d-" % key["bucket"]
        for name in self.entries():
            if want_b not in name:
                continue
            try:
                with open(os.path.join(self.directory, name), "rb") as f:
                    if f.read(len(_MAGIC)) != _MAGIC:
                        continue
                    header = json.loads(f.readline().decode("utf-8"))
            except Exception:  # noqa: BLE001 - diagnostics only
                continue
            bad = [fld for fld in KEY_FIELDS
                   if header.get(fld) != key[fld]]
            if bad:
                return ("entry %s exists for bucket %d but was built "
                        "under a different %s (e.g. %s=%r, want %r)"
                        % (name, key["bucket"], ", ".join(bad), bad[0],
                           header.get(bad[0]), key[bad[0]]))
        return ""
