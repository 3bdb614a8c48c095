"""chip_smoke.py's contract, as far as a CPU run can hold it to it.

In-process, no subprocess: without a TPU the script runs no phase and
says ``ok: false``; a phase that raises makes the exit code non-zero;
the compile cache goes where the environment says, else to one fixed
directory inside the checkout.
"""

import json
import os

import jax
import pytest

import chip_smoke
from rayfed_tpu.utils import platform as fed_platform


def _lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_device_check_refuses_the_cpu_backend():
    with pytest.raises(chip_smoke.SmokeError, match="no TPU"):
        chip_smoke.check_device(1)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_runs_no_phase_without_a_tpu(argv, capsys, monkeypatch):
    ran = []
    monkeypatch.setattr(
        chip_smoke, "PHASES",
        {n: [("never", lambda seed: ran.append(seed))] for n in (1, 4)},
    )
    assert chip_smoke.main(argv) != 0
    lines = _lines(capsys)
    assert not ran
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["error"]
    assert not any("phase" in line for line in lines)


def _fake_tpu(chips):
    return {"platform": "tpu", "kind": "fake", "count": chips}


def test_a_raising_phase_gives_a_nonzero_exit(capsys, monkeypatch):
    def boom(seed):
        raise RuntimeError("phase made to raise")

    monkeypatch.setattr(chip_smoke, "check_device", _fake_tpu)
    monkeypatch.setattr(
        chip_smoke, "PHASES",
        {1: [("boom", boom), ("after", lambda seed: {"seed": seed})]},
    )
    assert chip_smoke.main(["--seed", "7"]) != 0
    lines = _lines(capsys)
    by_phase = {line["phase"]: line for line in lines if "phase" in line}
    assert by_phase["boom"]["ok"] is False
    assert "phase made to raise" in by_phase["boom"]["error"]
    # Later phases still run and report; the verdict is the last line.
    assert by_phase["after"]["ok"] is True and by_phase["after"]["seed"] == 7
    assert lines[-1] == {
        "ok": False, "error": "failed phases: ['boom']",
        "device": _fake_tpu(1),
    }


def test_the_last_line_is_only_what_the_contract_fixes(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "check_device", _fake_tpu)
    monkeypatch.setattr(chip_smoke, "PHASES", {4: [("fine", lambda seed: {})]})
    assert chip_smoke.main(["--chips", "4"]) == 0
    lines = _lines(capsys)
    assert lines[-1] == {"ok": True, "device": _fake_tpu(4)}
    env = lines[0]
    assert env["phase"] == "environment"
    for key in ("jax", "jaxlib", "libtpu", "compile_cache_dir",
                "native_byte_path", "native_status", "gxx"):
        assert key in env, key
    assert {"wall_s", "compile_s"} <= set(lines[1])


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert fed_platform.use_compilation_cache() == str(tmp_path)
        # ...and sets no other directory in code.
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = fed_platform.use_compilation_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert fixed == os.path.join(repo, ".jax_cache")
        assert fixed == fed_platform.DEFAULT_COMPILATION_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_subslice_mesh_turns_the_persistent_cache_off(monkeypatch):
    """A multi-chip proper sub-slice mesh on an accelerator must not
    load executables from the persistent cache (they halt the chips on
    this jax/libtpu); every other mesh keeps it."""
    import types

    chips = [types.SimpleNamespace(id=i, platform="tpu") for i in range(4)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)

    def mesh(devices):
        return types.SimpleNamespace(
            devices=types.SimpleNamespace(flat=list(devices))
        )

    assert jax.config.jax_enable_compilation_cache
    try:
        assert not fed_platform.guard_subslice_mesh(mesh(chips[3:]))
        assert not fed_platform.guard_subslice_mesh(mesh(chips))
        cpus = [types.SimpleNamespace(id=i, platform="cpu") for i in range(2)]
        assert not fed_platform.guard_subslice_mesh(mesh(cpus))
        assert jax.config.jax_enable_compilation_cache
        assert fed_platform.guard_subslice_mesh(mesh(chips[2:]))
        assert not jax.config.jax_enable_compilation_cache
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
