"""CLI, training utils, and config setters.

Parity targets: ``byzpy/cli.py`` (version/doctor/list), ``byzpy/utils/
training.py`` (train_with_progress), ``byzpy/configs/actor.py`` (+ the
mesh analogue of configs/backend.py).
"""

import json

import pytest

from byzpy_tpu.cli import doctor_report, main
from byzpy_tpu.configs import (
    get_actor,
    get_default_mesh,
    set_actor,
    set_default_mesh,
    use_actor,
    use_mesh,
)
from byzpy_tpu.utils.training import train_with_progress
from byzpy_tpu.version import __version__


def test_cli_version(capsys):
    assert main(["version"]) == 0
    assert capsys.readouterr().out.strip() == __version__


def test_cli_doctor_json(capsys):
    assert main(["doctor", "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["jax"]["ok"]
    assert report["device_count"] >= 8  # virtual CPU mesh from conftest
    assert all(d["platform"] == "cpu" for d in report["devices"])
    assert report["version"] == __version__


def test_cli_list_kinds(capsys):
    assert main(["list", "aggregators"]) == 0
    out = capsys.readouterr().out
    for expected in ("CoordinateWiseMedian", "MultiKrum", "GeometricMedian",
                     "CenteredClipping", "SMEA"):
        assert expected in out
    assert main(["list", "attacks"]) == 0
    out = capsys.readouterr().out
    assert "SignFlipAttack" in out and "LittleAttack" in out
    assert main(["list", "pre-aggregators"]) == 0
    out = capsys.readouterr().out
    assert "Bucketing" in out and "NearestNeighborMixing" in out


def test_cli_lint_matches_module_entrypoint(capsys):
    # `byzpy-tpu lint` must be the exact same gate as
    # `python -m byzpy_tpu.analysis`: same findings, same exit codes
    import os

    fixtures = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures", "analysis"
    )
    tp = os.path.join(fixtures, "donation_tp.py")
    fp = os.path.join(fixtures, "donation_fp.py")

    assert main(["lint", fp]) == 0
    capsys.readouterr()
    assert main(["lint", tp]) == 1
    via_cli = capsys.readouterr().out

    from byzpy_tpu.analysis import main as lint_main

    assert lint_main([tp]) == 1
    via_module = capsys.readouterr().out
    assert via_cli == via_module
    assert "DONATION" in via_cli

    assert main(["lint", "--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rule in ("TRACE-DISPATCH", "DONATION", "AXIS-BINDING", "HOST-SYNC",
                 "ASYNC-BLOCKING", "PYTREE-REG", "UNUSED-IGNORE"):
        assert rule in listed


def test_doctor_report_probes_deps():
    report = doctor_report()
    assert report["flax"]["ok"] and report["optax"]["ok"]
    assert "native_shm_store" in report


def test_train_with_progress_runs_rounds_and_evals():
    class FakePS:
        def __init__(self):
            self.rounds = 0

        async def round(self):
            self.rounds += 1

    ps = FakePS()
    evals = []
    history = train_with_progress(
        ps, 25,
        eval_callback=lambda i: evals.append(i) or ps.rounds,
        eval_interval=10,
        progress=False,
    )
    assert ps.rounds == 25
    assert [i for i, _ in history] == [9, 19, 24]
    assert [r for _, r in history] == [10, 20, 25]


def test_actor_config_roundtrip():
    assert get_actor() == "thread"
    set_actor("process")
    try:
        assert get_actor() == "process"
        with use_actor("tpu"):
            assert get_actor() == "tpu"
        assert get_actor() == "process"
    finally:
        set_actor("thread")
    with pytest.raises(ValueError):
        set_actor("warp-drive")


def test_mesh_config_roundtrip(devices):
    assert get_default_mesh() is None
    mesh = get_default_mesh(create=True)
    assert mesh is not None and mesh.devices.size >= 8
    set_default_mesh(mesh)
    try:
        assert get_default_mesh() is mesh
    finally:
        set_default_mesh(None)
    with use_mesh(mesh):
        assert get_default_mesh() is mesh
    assert get_default_mesh() is None


def test_doctor_reports_devices_and_kind():
    """doctor names every device with its platform and device_kind; a
    device failure is reported as the exception, not swallowed."""
    from byzpy_tpu import cli

    report = cli.doctor_report()
    assert report["device_count"] >= 8
    assert all(
        d["platform"] == "cpu" and d["kind"] for d in report["devices"]
    )


def test_doctor_reports_device_exception_plainly(monkeypatch):
    import jax

    from byzpy_tpu import cli

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    report = cli.doctor_report()
    assert "no backend" in report["devices_error"]
    assert "devices" not in report


def test_bench_report_raises_on_device_failure(monkeypatch):
    """No ``{"error": ...}`` with exit 0: a bench that cannot reach its
    device fails."""
    import jax

    from byzpy_tpu import cli

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        cli.bench_report(n=8, d=256, repeat=1)


def test_cli_bench_runs_and_reports(capsys):
    from byzpy_tpu.cli import main

    rc = main(["bench", "--nodes", "8", "--dim", "1024", "--repeat", "2"])
    assert rc == 0
    import json

    report = json.loads(capsys.readouterr().out)
    assert report["shape"] == [8, 1024]
    for op in ("coordinate_median", "trimmed_mean", "multi_krum",
               "geometric_median"):
        assert "ms" in report[op], report[op]
        assert report[op]["ms"] > 0


def test_cli_study_parser_and_short_run(capsys):
    """The study subcommand wires mean-vs-robust through the real study
    harness (tiny round count; the accuracy contracts live in
    tests/test_robust_learning.py)."""
    pytest.importorskip("sklearn")
    from byzpy_tpu.cli import main

    assert main(["study", "--rounds", "2", "--aggregator", "median"]) == 0
    out = capsys.readouterr().out
    assert "| aggregator | sign_flip |" in out
    assert "median" in out and "mean" in out


def test_cli_study_choices_match_study_zoo():
    """The CLI's literal choices (kept import-light) must track the study
    module's zoo names."""
    from byzpy_tpu.cli import build_parser
    from byzpy_tpu.utils.robust_study import STUDY_AGGREGATORS, STUDY_ATTACKS

    parser = build_parser()
    sub = next(
        a for a in parser._subparsers._group_actions
    ).choices["study"]
    by_dest = {a.dest: a for a in sub._actions}
    assert tuple(by_dest["aggregator"].choices) == STUDY_AGGREGATORS
    assert tuple(by_dest["attack"].choices) == STUDY_ATTACKS


def test_compile_cache_helper_respects_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory in
    code (JAX reads the variable itself); unset, it resolves the same
    absolute in-checkout path from any working directory."""
    import os

    import jax

    from byzpy_tpu.utils import platform

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    assert platform.enable_compile_cache() == str(tmp_path / "elsewhere")
    assert platform.compile_cache_dir() == str(tmp_path / "elsewhere")
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    try:
        first = platform.enable_compile_cache()
        (tmp_path / "sub").mkdir()
        monkeypatch.chdir(tmp_path / "sub")
        second = platform.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert platform.compile_cache_dir() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
