"""chip_smoke.py off the chip: it refuses to run, its stage functions
work (the "run it here first" of the on-chip-measurement guide: the
same code at a tiny size on the CPU, kernels forced in interpreter
mode), and the CLI builds a meshed engine on the XLA step path
whatever backend it finds."""

import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclass resolves annotations there
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero, says which
    platform it found, and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "default platform is 'cpu'" in proc.stdout
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_stages_at_tiny_size(tmp_path, monkeypatch):
    """The stage functions, same code as on the chip, at a tiny size:
    the kernels the TPU default selects are forced on (interpreter mode
    here), and each stage's own oracles must hold. `stage_widest` is
    left to the chip: it is the flagship + oracle pattern (run_cli,
    check_stream_resolved, lane_results against the CPU backend) on
    another machine, and another ten seconds of CPU compiles here."""
    monkeypatch.setenv("MADSIM_TPU_PALLAS_POP", "1")
    monkeypatch.setenv("MADSIM_TPU_PALLAS_MEGAKERNEL", "1")
    monkeypatch.delenv("MADSIM_TPU_STATS", raising=False)
    cs = _load_chip_smoke()
    out = str(tmp_path)

    flagship = cs.stage_flagship(out, 32, 16, on_chip=False)
    assert flagship["run"].eng._pallas_interpret  # forced, off the chip
    assert flagship["counts"]["completed"] >= 32
    assert flagship["counts"]["coverage_slots"] > 0

    oracle = cs.stage_oracle(flagship["run"], out, 32, 16, 8)
    assert oracle["counts"]["trail_lanes"] == 32
    assert oracle["counts"]["trail_checkpoints"] > 0
    assert oracle["counts"]["cpu_lanes"] == 8

    hunt = cs.stage_hunt(out, 64, 32)
    assert hunt["counts"]["failing"] >= 1
    assert hunt["counts"]["trail_checkpoints"] >= 1

    mesh = cs.stage_mesh(flagship["run"], out, 32, 16, 4)  # 8 virtual devices
    assert mesh["counts"]["lanes_per_device"] == 4
    assert mesh["counts"]["lane_leaves_on_all_devices"] > 0

    # a wrong result is a SmokeFailure naming what differed
    flagship["run"].agg["failing"].append((7, 99))
    with pytest.raises(cs.SmokeFailure, match="failing differ"):
        cs.check_same_stream(
            flagship["run"], oracle["run"],
            os.path.join(out, "flagship"), os.path.join(out, "oracle"), "x",
        )


def test_meshed_engine_takes_the_xla_step_path_on_any_backend(monkeypatch):
    """`--devices N > 1` (CLI flag or fleet spec field) builds the engine
    with both Pallas kernels off even where the backend default is ON,
    so the four-chip path runs with no MADSIM_TPU_* variable set."""
    import jax

    from madsim_tpu.__main__ import _build_engine
    from madsim_tpu.fleet.store import normalize_spec, spec_to_args

    monkeypatch.delenv("MADSIM_TPU_PALLAS_POP", raising=False)
    monkeypatch.delenv("MADSIM_TPU_PALLAS_MEGAKERNEL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def build(devices):
        return _build_engine(spec_to_args(normalize_spec(
            {"machine": "raft", "rng_stream": 3, "devices": devices}
        )))

    single = build(0)  # the TPU default: both kernels, compiled
    assert single.use_pallas_pop and single.use_megakernel
    assert not single._pallas_interpret
    meshed = build(4)
    assert not meshed.use_pallas_pop and not meshed.use_megakernel
    assert meshed.config == dataclasses.replace(
        single.config, pallas_megakernel=False
    )
