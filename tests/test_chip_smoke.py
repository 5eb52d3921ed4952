"""chip_smoke.py's contract, rehearsed on the CPU.

The smoke is the first command sent to the chip; a syntax error or API
drift inside it would only surface there, at chip-minute prices. These
tests run it under an EXPLICIT ``JAX_PLATFORMS=cpu`` (the only way it
runs off a TPU) and pin what the driver relies on: never exit 0 and
never print the ``{"ok": true}`` result without an accelerator, exit 1
when a leg raises, exit 2 when JAX silently found no chip.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(argv, env, timeout=1200):
    proc = subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=ROOT)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert not any(ln.startswith('{"ok"') for ln in lines), \
        "a run without a TPU printed a result line"
    return proc, lines


def _cpu_env(n):
    sys.path.insert(0, ROOT)
    from __graft_entry__ import virtual_cpu_env
    return virtual_cpu_env(n)


# every case that starts a fresh interpreter (the first compiles
# ResNet-50 three times over) is slow, outside the tier-1 budget
slow = pytest.mark.slow


def test_unknown_device_kind_fails_loudly(capsys):
    """The smoke's peak check reads the benchmark's table (the only one
    in the repository): the chip the cells run on is known, and a
    ``device_kind`` without a row fails by name, as ``benchmark/run.py``
    would later."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    assert chip_smoke.peaks_known("TPU v5 lite")
    assert "197 bf16 TFLOP/s" in capsys.readouterr().out
    assert not chip_smoke.peaks_known("TPU v0 imaginary")
    out = capsys.readouterr().out
    assert out.startswith("FAILED peaks:") and "TPU v0 imaginary" in out \
        and "benchmark/peaks.py" in out


@slow
def test_rehearsal_runs_every_leg_and_never_passes():
    import chip_smoke
    proc, lines = _run([sys.executable, SMOKE], _cpu_env(2))
    assert proc.returncode == chip_smoke.EXIT_REHEARSAL, \
        proc.stdout[-3000:] + proc.stderr[-3000:]
    assert lines[0].startswith("chip_smoke: platform=cpu ") and \
        "count=2" in lines[0] and "jax=" in lines[0] and \
        "libtpu=" in lines[0], lines[0]
    assert "REHEARSAL" in lines[1] and "NO device" in lines[1]
    for leg in ("train", "serve", "decode"):
        assert any(ln.startswith("[%s] OK" % leg) for ln in lines), leg
    assert any("TOY-SIZED" in ln for ln in lines)
    assert "no device was checked" in lines[-1]


@slow
def test_leg_that_raises_exits_nonzero_and_names_the_leg():
    code = ("import sys, chip_smoke\n"
            "def boom(env):\n"
            "    raise RuntimeError('forced')\n"
            "chip_smoke.LEGS = (('decode', boom),)\n"
            "sys.exit(chip_smoke.main())\n")
    proc, lines = _run([sys.executable, "-c", code], _cpu_env(1),
                       timeout=300)
    import chip_smoke
    assert proc.returncode == chip_smoke.EXIT_LEG_FAILED, proc.stderr[-2000:]
    assert "FAILED decode" in proc.stdout
    assert "RuntimeError: forced" in proc.stderr


@slow
def test_no_accelerator_without_explicit_cpu_is_refused():
    """JAX falling back to the CPU on its own is the failure this
    script exists to catch: exit 2, no leg runs."""
    env = _cpu_env(1)
    del env["JAX_PLATFORMS"]
    proc, lines = _run([sys.executable, SMOKE], env, timeout=300)
    import chip_smoke
    assert proc.returncode == chip_smoke.EXIT_NO_CHIP, proc.stderr[-2000:]
    assert "refusing to run" in proc.stderr
    assert not any(ln.startswith("[") for ln in lines)
