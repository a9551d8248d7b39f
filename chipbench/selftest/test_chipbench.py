"""Rehearsals of the benchmark on the CPU backend (``python -m pytest
chipbench/selftest -q``). They check paths, control flow and what is
counted; no number here is a device metric."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, reference, seeded, stated_types, trace_reduce
from chipbench.selftest import manifest_rules

ROOT = harness.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
# the five the driver reads, and the numbers compared beside their limits, last in the line
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _real_manifest():
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path, encoding="utf-8") as fh:
        raw = fh.read()
    return json.loads(raw), len(raw.encode("utf-8"))


def _toy_manifest():
    """The toy cells with the real manifest's metrics, every per-layer
    metric offered to every toy cell (a reader with nothing to read
    returns nothing)."""
    toy = harness.load_json(HERE, "toy", "manifest.json")
    real, _ = _real_manifest()
    cells = [c["name"] for c in toy["workloads"]]
    toy["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"} for m in real["end_to_end"]
        if m["name"] in ("setup_s", "train_samples_per_s")
    ]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": cells}
        for name in readers
    ]
    return toy


def _run_toy(cell, *, trace, control=None, seed=2**31 + 11):
    import jax

    toy = _toy_manifest()
    chips = harness.find_cell(toy, cell)["chips"]
    lines = []
    line = harness.run_cell(
        toy, cell, seed=seed, seconds=0.5, trace=trace, devices=jax.devices()[:chips],
        t_process=time.perf_counter(), control=control, emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


# -- the command ------------------------------------------------------------


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    real, _ = _real_manifest()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         real["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert done.returncode != 0
    assert "TPU" in done.stderr
    assert '"correct"' not in done.stdout


def test_manifest_meets_the_contracts_rules():
    manifest, size = _real_manifest()
    assert manifest_rules.check(manifest, ROOT, size) == []
    for cell in manifest["workloads"]:
        entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        config = harness.load_json(ROOT, entry["file"])
        mix = harness.load_json(ROOT, "chipbench", "traffic", cell["traffic"] + ".json")
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "drivers", mix["driver"] + ".py"))
        assert sorted(entry["reduced"]) == sorted(config["reduced"])


def test_manifest_rules_refuse_what_the_ledger_refused():
    manifest, size = _real_manifest()
    manifest["per_layer"][0]["layer"] = "round path"  # PR 22's fault
    manifest["end_to_end"][0]["unit"] = "samples per second"
    found = manifest_rules.check(manifest, ROOT, size)
    assert any("is no identifier" in f for f in found)
    assert any("unit" in f for f in found)


# -- a run, end to end, at toy size -----------------------------------------


@pytest.mark.parametrize("cell", ["toy.trimmed-signflip", "toy.krum-empire",
                                  "toy.trimmed-signflip.mesh4"])
def test_toy_cell_prints_the_contracts_line(cell):
    line = _run_toy(cell, trace=False)
    assert set(line) == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"] and all(
        set(said) == {"value", "must_be", "limit", "ok"} and said["ok"] is True
        for said in line["compared"].values())
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell", ["toy.trimmed-signflip", "toy.trimmed-signflip.mesh4"])
def test_toy_cell_traced_reports_layers_and_breakdown(cell):
    line = _run_toy(cell, trace=True)
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    assert line["correct"] is True
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] >= line["device"]["busy_s"]
    want = {"robust_overhead_pct.train", "step_device_ms.train", "kernel_route.train",
            "device_idle_pct.train", "peak_hbm_gb.train"}
    assert want <= set(line["metrics"])
    assert "agg_roofline.train" not in line["metrics"]  # no peak for a CPU: nothing to read
    assert ("collective_mb_per_dev.train" in line["metrics"]) == cell.endswith("mesh4")
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_comes_out_not_correct(control, capsys):
    """The program's own bf16 gradient rows (``grad_dtype``) and its own
    bf16 activations (the model's ``dtype``) in the place of the stated
    float32: both are read off the step's lowered text."""
    line = _run_toy("toy.trimmed-signflip", trace=False, control=control)
    assert line["correct"] is False
    compared = [json.loads(text) for text in capsys.readouterr().out.splitlines()
                if '"compared"' in text]
    narrow = next(c for c in compared if c["compared"] == "elements_narrower_than_float32")
    assert narrow["value"] > 0 and narrow["ok"] is False


def test_the_real_configuration_lists_both_controls_and_states_its_type():
    config = harness.load_json(ROOT, "chipbench", "configs", "resnet18-cifar-ps.json")
    assert config["stated_dtype"] == "float32"
    assert set(config["controls"]) == {"grad_bf16", "model_bf16"}


def test_narrow_types_are_read_off_a_lowered_text():
    import jax
    import jax.numpy as jnp

    def sound(x):
        return jnp.mean(jnp.sort(x, axis=0)[2:6], axis=0)

    def rows_in_bf16(x):  # bf16 rows reduced into f32: the emitted aggregate is f32
        return jnp.mean(jnp.sort(x.astype(jnp.bfloat16), axis=0)[2:6].astype(jnp.float32), axis=0)

    def rows_in_int8(x):
        return jnp.mean(jnp.round(x * 16).astype(jnp.int8).astype(jnp.float32), axis=0) / 16

    x = jnp.asarray(_rows(d=512))
    text = {fn.__name__: jax.jit(fn).lower(x).as_text() for fn in (sound, rows_in_bf16, rows_in_int8)}
    assert stated_types.narrow_elements(text["sound"], "float32") == 0
    assert stated_types.narrow_elements(text["rows_in_bf16"], "float32") == 8 * 512
    assert stated_types.narrow_elements(text["rows_in_int8"], "float32") == 8 * 512
    assert stated_types.narrow_elements(text["rows_in_bf16"], "bfloat16") == 0
    assert stated_types.bits_and_kind("f8E4M3FN") == (8, "float")
    assert stated_types.bits_and_kind("i1") == (1, "int")
    assert stated_types.bits_and_kind("index") == (None, None)


def test_short_mantissas_show_bf16_rows_even_where_the_mean_is_taken_in_f32():
    import jax.numpy as jnp

    x = _rows(d=1 << 16)
    sound = np.asarray(reference.trimmed_mean(jnp.asarray(x), f=2))
    assert reference.short_mantissa_share(sound) < 0.01  # about 2**-8
    rounded = jnp.asarray(x).astype(jnp.bfloat16)
    emitted = np.asarray(reference.trimmed_mean(rounded, f=2).astype(jnp.float32))
    assert reference.short_mantissa_share(emitted) == 1.0
    reduced_in_f32 = np.asarray(reference.trimmed_mean(rounded.astype(jnp.float32), f=2))
    assert reference.short_mantissa_share(reduced_in_f32) > 0.5
    assert reference.short_mantissa_share(reduced_in_f32, low_bits=16) < 0.5  # the old test


def test_memory_peak_adds_only_what_one_reading_holds():
    class Chip:
        platform, device_kind = "tpu", "fake"

        def __init__(self, **stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip(bytes_in_use=2, bytes_reserved=40, peak_bytes_in_use=30, peak_bytes_reserved=41),
             Chip(bytes_in_use=1, bytes_reserved=1, peak_bytes_in_use=50, peak_bytes_reserved=3)]
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                      trace=False, devices=chips, t_process=0.0)
    assert ctx.memory_peak() == 50  # not 30 + 41, not 50 + 3


@pytest.mark.parametrize("fault", ["state_unchanged", "part_of_the_batch_left_out"])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from byzpy_tpu.parallel import ps

    real = ps.jit_ps_train_step

    def copied(tree):  # the real step donates what it is given
        return jax.tree_util.tree_map(jnp.copy, tree)

    def broken(bundle, aggregate, cfg, **kwargs):
        step, opt_state = real(bundle, aggregate, cfg, **kwargs)

        def unchanged(params, opt, xs, ys, key):
            _, _, metrics = step(copied(params), copied(opt), xs, ys, key)
            return params, opt, metrics

        def half_batch(params, opt, xs, ys, key):
            half = xs.shape[1] // 2
            return step(params, opt, xs.at[:, half:].set(xs[:, :half]),
                        ys.at[:, half:].set(ys[:, :half]), key)

        wrapped = unchanged if fault == "state_unchanged" else half_batch
        wrapped._cache_size = step._cache_size
        wrapped.lower = step.lower
        return wrapped, opt_state

    monkeypatch.setattr(ps, "jit_ps_train_step", broken)
    assert _run_toy("toy.trimmed-signflip", trace=False)["correct"] is False


# -- the reference ----------------------------------------------------------


def _rows(seed=5, n=8, d=4096):
    return np.asarray(seeded.make_matrix(seed, n, d))


def test_reference_agrees_with_the_program_and_not_with_bf16_rows():
    import jax.numpy as jnp

    from byzpy_tpu.ops import robust

    x = _rows()
    lowered = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    for ours, theirs in [
        (lambda m: reference.trimmed_mean(m, f=2), lambda m: robust.trimmed_mean(m, f=2)),
        (lambda m: reference.multi_krum(m, f=2, q=4), lambda m: robust.multi_krum(m, f=2, q=4)),
    ]:
        want = np.asarray(ours(jnp.asarray(x)))
        got = np.asarray(theirs(jnp.asarray(x)))
        # f32 reorderings of a mean of <= 6 values: a few ulp of the largest
        np.testing.assert_allclose(got, want, rtol=0, atol=4e-6)
        off = np.asarray(theirs(jnp.asarray(lowered)))
        assert np.max(np.abs(off - want)) > 1e-3  # bf16 rows: 2**-9 relative


def test_reference_krum_scores_match_the_definition():
    x = _rows(n=6, d=64).astype(np.float64)
    f = 1
    want = []
    for i in range(6):
        d2 = sorted(float(np.sum((x[i] - x[j]) ** 2)) for j in range(6) if j != i)
        want.append(sum(d2[: 6 - f - 1]))
    got = np.asarray(reference.krum_scores(np.asarray(x, np.float32), f=f))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_reference_models_match_the_programs_at_the_same_weights():
    import jax

    from byzpy_tpu.models.nets import ResNet18, make_bundle, mnist_mlp

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3)))
    bundle = make_bundle(ResNet18(num_classes=10), (1, 32, 32, 3), seed=0)
    shapes = jax.eval_shape(lambda: bundle.params)
    params = seeded.make_params(shapes, 7)
    arch = harness.load_json(ROOT, "chipbench", "configs",
                             "resnet18-cifar-ps.json")["reference"]["arch"]
    got = np.asarray(bundle.apply_fn(params, x))
    want = np.asarray(reference.resnet_gn_logits(params, x, arch))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    mlp = mnist_mlp(0, hidden=16)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (3, 28, 28, 1)))
    np.testing.assert_allclose(
        np.asarray(mlp.apply_fn(mlp.params, x)),
        np.asarray(reference.mlp_logits(mlp.params, x, {})), rtol=1e-5, atol=1e-6,
    )


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_of_leaf_and_median():
    assert reference.worst_leaf_norm_gap([1.0, 2.0, 3.1], [1.0, 2.0, 3.0]) == pytest.approx(0.1 / 3)
    # a near-zero leaf is measured against the median leaf's norm
    assert reference.worst_leaf_norm_gap([1e-6, 2.0, 3.0], [2e-6, 2.0, 3.0]) == pytest.approx(5e-7)


def test_seeds_past_two_to_the_31_make_different_weights():
    import jax

    shapes = {"params": {"Dense_0": {"kernel": jax.ShapeDtypeStruct((4, 3), np.float32),
                                     "bias": jax.ShapeDtypeStruct((3,), np.float32)}}}
    a = seeded.make_params(shapes, 5)
    b = seeded.make_params(shapes, 5 + 2**31)
    c = seeded.make_params(shapes, 5)
    assert not np.array_equal(a["params"]["Dense_0"]["kernel"], b["params"]["Dense_0"]["kernel"])
    assert np.array_equal(a["params"]["Dense_0"]["kernel"], c["params"]["Dense_0"]["kernel"])
    assert not np.any(np.asarray(a["params"]["Dense_0"]["bias"]))


# -- the reduction from a trace ---------------------------------------------


def test_interval_arithmetic():
    ev = trace_reduce.Event
    dev = trace_reduce.DeviceTrace("d", ops=[ev("a", 0, 10), ev("b", 5, 20), ev("a", 40, 50)])
    assert trace_reduce.busy_seconds(dev, 0, 100) == pytest.approx(30e-9)
    assert trace_reduce.busy_seconds(dev, 8, 45) == pytest.approx(17e-9)
    assert trace_reduce.top_ops(dev, 0, 100, k=1) == [["a", pytest.approx(20e-9)]]
    reduced = trace_reduce.Reduced(devices=[dev], spans=[ev("chipbench.window", 0, 100),
                                                         ev("chipbench.sync", 18, 42)])
    gaps = trace_reduce.idle_gaps(reduced, dev, 0, 100, k=2)
    assert gaps == [["window", pytest.approx(50e-9)], ["sync", pytest.approx(20e-9)]]


def test_reduction_of_the_recorded_chip_trace_gives_what_is_written_beside_it():
    path = os.path.join(HERE, "recorded", "toy_step.xplane.pb")
    want = harness.load_json(HERE, "recorded", "toy_step.expected.json")
    reduced = trace_reduce.reduce_trace(path)
    assert [d.name for d in reduced.devices] == want["devices"]
    window = trace_reduce.span(reduced, "window")
    dev = reduced.devices[0]
    busy = trace_reduce.busy_seconds(dev, window.start, window.end)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert (window.end - window.start) * 1e-9 == pytest.approx(want["window_s"], rel=1e-9)
    top = trace_reduce.top_ops(dev, window.start, window.end, k=3)
    assert [name for name, _ in top] == want["top_ops"]
    runs = trace_reduce.module_runs(dev, want["module"], window.start, window.end)
    assert len(runs) == want["module_runs"]
