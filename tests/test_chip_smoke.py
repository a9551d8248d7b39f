"""The chip smoke's strictness and logic, on the CPU.

``chip_smoke.main()`` accepts nothing but a TPU; its phases are functions
of their sizes, so they are debugged here at toy size (ResNet-18 →
``mnist_mlp``, small ``d``, kernels interpreted) on the 8-device CPU mesh
before chip time is spent. Beside them: the other place that used to
hide a missing device — ``__graft_entry__._ensure_devices`` — now fails.
"""

from __future__ import annotations

import os
import subprocess
import sys
from functools import partial

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_cpu(script: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )


def test_main_refuses_cpu_before_compiling(monkeypatch, capsys):
    """main() under JAX_PLATFORMS=cpu: non-zero, names the missing TPU,
    and compiles nothing on the way."""
    compiled = []
    monkeypatch.setattr(
        jax.monitoring, "record_event_duration_secs",
        lambda event, *a, **k: compiled.append(event), raising=True,
    )
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err and "'cpu'" in captured.err
    assert not [e for e in compiled if "compile" in e]


def test_script_exits_nonzero_on_cpu_and_prints_no_result():
    proc = _run_cpu("chip_smoke.py")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_phase_device_reports_versions():
    rec = chip_smoke.phase_device()
    assert rec["platform"] == "cpu" and rec["count"] >= 8
    assert rec["jax"] == jax.__version__
    assert rec["device_kind"]


def test_phase_kernels_toy():
    """Every case of the kernel table, forced through the interpreted
    kernels at toy size (one shape with a ragged tail per dtype), meets its
    own tolerance against the XLA route."""
    rec = chip_smoke.phase_kernels(
        [(8, 640, "float32"), (16, 520, "bfloat16")],
        kernel_route="1", require_mosaic=False,
    )
    assert len(rec["cases"]) == 2 * 23
    assert os.environ.get("BYZPY_TPU_PALLAS") is None


def test_phase_kernels_demands_mosaic():
    """On the CPU the kernel route has no Mosaic custom call: with
    require_mosaic the phase fails instead of passing an interpreted
    kernel."""
    with pytest.raises(chip_smoke.SmokeFailure, match="no Mosaic custom call"):
        chip_smoke.phase_kernels([(8, 256, "float32")], kernel_route="1")


def _toy_bundle():
    from byzpy_tpu.models import mnist_mlp

    return mnist_mlp(seed=0, hidden=32)


def test_phase_trainer_and_mesh_toy():
    """The one-device rounds, then the same rounds over the 8-device CPU
    mesh: placement, the collective law, no whole-matrix collective, and
    the one-device loss sequence."""
    single = chip_smoke.phase_trainer(
        _toy_bundle, input_shape=(28, 28, 1), batch=16, steps=5,
        learning_rate=0.05, expect_kernel=False, baseline3=False,
        platform="cpu",
    )
    losses = {name: run["losses"] for name, run in single["runs"].items()}
    assert set(losses) == {"trimmed_mean/sign_flip", "multi_krum/empire"}
    mesh = chip_smoke.phase_mesh_trainer(
        _toy_bundle, losses, input_shape=(28, 28, 1), n_chips=8, batch=16,
        steps=5, learning_rate=0.05, platform="cpu",
    )
    for run in mesh["runs"].values():
        assert run["compilations"] == 1
        assert run["largest_collective_bytes"] < run["whole_matrix_bytes"]


def test_phase_serving_toy():
    rec = chip_smoke.phase_serving(
        _toy_bundle, dim=384, clients=12, cohorts=(12, 7, 9),
        step_capacity=8, step_cohort=5, platform="cpu",
    )
    assert [r["m"] for r in rec["frontend_rounds"]] == [12, 7, 9]
    assert rec["ragged_compile_entries"] == 1


def test_run_phase_prints_failure_and_reraises(capsys):
    def boom():
        raise chip_smoke.SmokeFailure("nope")

    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run_phase("x", boom)
    line = capsys.readouterr().out.strip()
    assert '"ok": false' in line and "nope" in line


def test_ensure_devices_raises_when_short_and_not_cpu_pinned(monkeypatch):
    import __graft_entry__ as graft

    have = len(jax.devices())
    graft._ensure_devices(have)  # enough devices: nothing to do
    monkeypatch.setattr(
        graft, "_platform_pinned_to_cpu", lambda: False, raising=True
    )
    with pytest.raises(RuntimeError, match=f"{have + 1} devices"):
        graft._ensure_devices(have + 1)


def test_interpret_on_tpu_is_impossible(monkeypatch):
    from byzpy_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert pk._resolve_interpret(None) is False
    assert pk._resolve_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret=True on a TPU"):
        pk._resolve_interpret(True)
    monkeypatch.setattr(pk, "_on_tpu", lambda: False)
    assert pk._resolve_interpret(None) is True


def test_sharded_operand_never_reaches_a_pallas_call(monkeypatch):
    """The coordinate-wise family on a feature-sharded operand (Auto
    mesh) with the kernels forced on: the trace must stay on XLA — a
    pallas_call there all-gathers the whole matrix."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from byzpy_tpu.ops import pallas_kernels as pk
    from byzpy_tpu.ops import robust
    from byzpy_tpu.parallel.mesh import node_mesh

    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    mesh = node_mesh(8)
    x = jax.device_put(
        jnp.ones((8, 1024), jnp.float32) * jnp.arange(8.0)[:, None],
        NamedSharding(mesh, P(None, "nodes")),
    )
    assert not pk.sharding_allows_pallas(x)
    for fn in (
        robust.coordinate_median,
        partial(robust.trimmed_mean, f=2),
        partial(robust.mean_of_medians, f=2),
    ):
        jaxpr = str(jax.make_jaxpr(fn)(x))
        assert "pallas_call" not in jaxpr, fn
    # unsharded, the same calls do take the kernel
    assert "pallas_call" in str(
        jax.make_jaxpr(robust.coordinate_median)(jnp.ones((8, 1024)))
    )


def test_s4_kernel_switches_raise_on_tpu(monkeypatch):
    """The s4 Pallas kernels do not lower on this Mosaic: on a TPU their
    opt-in raises with the compiler's words instead of falling back."""
    import jax.numpy as jnp

    from byzpy_tpu.ops import pallas_kernels as pk
    from byzpy_tpu.parallel import quantization as qz

    x = jnp.ones((8, 512), jnp.float32)
    packed = qz.encode_blockwise(x, "s4", use_pallas=False)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="float32 -> uint8"):
        qz.encode_blockwise(x, "s4", use_pallas=True)
    with pytest.raises(NotImplementedError, match="uint8 -> float32"):
        qz.dequantize_blockwise(packed, use_pallas=True)
    with pytest.raises(NotImplementedError, match="ROADMAP S4"):
        pk.ragged_segment_sum_dequant_pallas(
            packed.values, packed.scales, jnp.ones((1, 8)) / 8, mode="s4",
            block=packed.block, d=512,
        )
