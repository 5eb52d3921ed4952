"""Shared build-and-load for the native runtime libs.

Compiles C++ sources into ``runtime/_build/`` (gitignored — no binary
artifacts in the tree, no in-place rewrites of package files) and loads
them with ctypes. A library is loaded only when its stamp says it was
built from the current source, with the current flags, on a host with
this CPU: ``_build/`` travels with a copied tree, and a ``-march=native``
build from another machine would die on an illegal instruction. A build
that fails raises; nothing older is loaded in its place.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "_build")


class NativeBuildError(RuntimeError):
    """The native library could not be built or loaded on this host."""


def _host_cpu():
    """What ``-march=native`` keys on: the CPU's feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return platform.processor()


def _stamp(src, cmd):
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(cmd).encode())
    h.update(platform.machine().encode())
    h.update(_host_cpu().encode())
    return h.hexdigest()


def load_native(src_name, lib_name, extra_flags=()):
    """Return a ctypes.CDLL for runtime/<src_name>, built on this host.

    Rebuilds to _build/<lib_name> unless its stamp (source hash, compile
    flags, host CPU flags) matches; raises :class:`NativeBuildError` when
    the build or the load fails.
    """
    src = os.path.join(_DIR, src_name)
    so = os.path.join(_BUILD_DIR, lib_name)
    cmd = ["g++", "-O3", "-std=c++14", "-shared", "-fPIC", "-pthread",
           *extra_flags]
    want = _stamp(src, cmd)
    try:
        with open(so + ".stamp") as f:
            fresh = f.read() == want and os.path.exists(so)
    except OSError:
        fresh = False
    if not fresh:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = "%s.tmp-%d" % (so, os.getpid())
        try:
            subprocess.run(cmd + [src, "-o", tmp], check=True,
                           capture_output=True, timeout=180)
        except (OSError, subprocess.SubprocessError) as e:
            raise NativeBuildError(
                "building %s failed: %s\n%s" % (
                    lib_name, e,
                    (getattr(e, "stderr", b"") or b"").decode(
                        "utf-8", "replace")[-2000:])) from e
        os.replace(tmp, so)  # atomic: never load a half-written .so
        with open(so + ".stamp", "w") as f:
            f.write(want)
    try:
        return ctypes.CDLL(so)
    except OSError as e:
        raise NativeBuildError("loading %s failed: %s" % (so, e)) from e
