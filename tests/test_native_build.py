"""runtime/_native_build.load_native: a library is loaded only when it
was built from the current source, with the current flags, on this
host — ``_build/`` travels with a copied tree, and the stamp is what
keeps another machine's ``-march=native`` build from being loaded."""
import os
import shutil

import pytest

from mxnet_tpu.runtime import _native_build as nb

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no g++")


@pytest.fixture
def sandbox(tmp_path, monkeypatch):
    monkeypatch.setattr(nb, "_DIR", str(tmp_path))
    monkeypatch.setattr(nb, "_BUILD_DIR", str(tmp_path / "_build"))
    src = tmp_path / "answer.cpp"
    src.write_text('extern "C" int answer() { return 41; }\n')
    return tmp_path, src


def test_builds_stamps_and_reuses(sandbox):
    tmp, _src = sandbox
    assert nb.load_native("answer.cpp", "libanswer.so").answer() == 41
    so = tmp / "_build" / "libanswer.so"
    stamp = tmp / "_build" / "libanswer.so.stamp"
    assert so.exists() and len(stamp.read_text()) == 64
    built = os.stat(so).st_mtime_ns
    nb.load_native("answer.cpp", "libanswer.so")
    assert os.stat(so).st_mtime_ns == built, "a fresh build was rebuilt"


def test_foreign_or_stale_build_is_rebuilt_never_loaded(sandbox):
    tmp, src = sandbox
    build = tmp / "_build"
    build.mkdir()
    # a _build/ carried over from another machine: the right name, a
    # stamp this host would not have written, bytes it cannot run
    (build / "libanswer.so").write_bytes(b"not an ELF from here")
    (build / "libanswer.so.stamp").write_text("0" * 64)
    assert nb.load_native("answer.cpp", "libanswer.so").answer() == 41
    good = (build / "libanswer.so.stamp").read_text()
    assert good != "0" * 64
    # an edited source invalidates the build even with an older mtime
    src.write_text('extern "C" int answer() { return 42; }\n')
    os.utime(src, (1, 1))
    shutil.copy(build / "libanswer.so", build / "libanswer2.so")
    shutil.copy(build / "libanswer.so.stamp", build / "libanswer2.so.stamp")
    assert nb.load_native("answer.cpp", "libanswer2.so").answer() == 42
    # ... and so do other flags (the stamp covers the command line)
    edited = (build / "libanswer2.so.stamp").read_text()
    assert edited != good
    shutil.copy(build / "libanswer2.so", build / "libanswer3.so")
    shutil.copy(build / "libanswer2.so.stamp", build / "libanswer3.so.stamp")
    nb.load_native("answer.cpp", "libanswer3.so", extra_flags=("-O1",))
    assert (build / "libanswer3.so.stamp").read_text() != edited


def test_failed_build_raises_and_loads_nothing_older(sandbox):
    tmp, src = sandbox
    nb.load_native("answer.cpp", "libanswer.so")
    src.write_text("this is not C++\n")
    with pytest.raises(nb.NativeBuildError) as err:
        nb.load_native("answer.cpp", "libanswer.so")
    assert "libanswer.so" in str(err.value) and "error" in str(err.value)
