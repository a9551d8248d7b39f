"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, in ONE process, through the public entry
points, at the full width of ResNet-18, on a TPU and nothing else:

1. device    every ``jax.devices()`` entry is a TPU
2. kernels   every Pallas kernel the default dispatch reaches is compiled
             by Mosaic (not interpreted) and agrees with its XLA twin
3. trainer   the fused robust PS round (``parallel.ps.jit_ps_train_step``)
             trains ResNet-18 under attack on one chip; BASELINE config #3
             (MNIST MLP, below the kernel floor) runs the XLA route
4. serving   three ragged rounds through one ``ServingFrontend``, the
             ragged serving PS step, the donated masked finalize
5. mesh      phase 3's ResNet-18 rounds over ``node_mesh(4)`` when the
             host has four chips

One JSON line per phase, then one summary line naming the device. Exit
code 0 only when every phase passed. No flag or environment variable
turns this into a CPU run; ``tests/test_chip_smoke.py`` calls the phase
functions at toy size on the CPU mesh instead. Wall times printed here
are information for the next builder, labelled with the device — they are
not metrics and go in no record as such.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SmokeFailure(AssertionError):
    """A phase assertion did not hold."""


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Compile accounting (jax.monitoring), per phase: seconds in the backend's
# compile-or-fetch door, how much of that was fetching executables back
# from the persistent cache, and the cache's hits/misses (a program that
# compiles in under the cache's 0.1 s floor is never stored: always a miss).
# ---------------------------------------------------------------------------

_COMPILE = {"seconds": 0.0, "retrieval": 0.0, "hits": 0, "misses": 0}


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += seconds
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _COMPILE["retrieval"] += seconds


def _on_event(event: str, **_: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _COMPILE["misses"] += 1


_LISTENING = False


def _listen_for_compiles() -> None:
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _LISTENING = True


def run_phase(name: str, fn: Callable[[], Dict[str, Any]]) -> Dict[str, Any]:
    """Run one phase, print its JSON line, re-raise its failure."""
    _listen_for_compiles()
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    record: Dict[str, Any] = {"phase": name, "ok": False}
    try:
        record.update(fn())
        record["ok"] = True
        return record
    except BaseException as exc:
        record["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        raise
    finally:
        record["seconds"] = round(time.perf_counter() - t0, 2)
        record["compile_seconds"] = round(
            _COMPILE["seconds"] - before["seconds"], 2
        )
        record["cache_retrieval_seconds"] = round(
            _COMPILE["retrieval"] - before["retrieval"], 2
        )
        record["cache_hits"] = _COMPILE["hits"] - before["hits"]
        record["cache_misses"] = _COMPILE["misses"] - before["misses"]
        dev = jax.devices()[0]
        record["device"] = f"{dev.platform}:{dev.device_kind}x{len(jax.devices())}"
        print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# Phase 1: device
# ---------------------------------------------------------------------------


def tpu_devices() -> list:
    """``jax.devices()`` when every one of them is a TPU; else raises
    with the reason. Compiles nothing."""
    devices = jax.devices()
    wrong = sorted({d.platform for d in devices} - {"tpu"})
    if wrong:
        raise SmokeFailure(
            f"chip_smoke needs a TPU: jax.devices() reports platform(s) "
            f"{wrong} ({len(devices)} device(s), JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); no flag turns this "
            f"into a CPU run"
        )
    return devices


def phase_device() -> Dict[str, Any]:
    import jaxlib

    devices = jax.devices()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    from byzpy_tpu.utils.platform import compile_cache_dir

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": compile_cache_dir(),
        "asserted": ["every device's platform"],
    }


# ---------------------------------------------------------------------------
# Phase 2: kernels
# ---------------------------------------------------------------------------

_MOSAIC_MARK = "tpu_custom_call"


@contextlib.contextmanager
def _pallas_dispatch(value: Optional[str]) -> Iterator[None]:
    """``BYZPY_TPU_PALLAS`` for the dispatches traced inside: ``None`` =
    the default (``auto``) dispatch, ``"0"`` = the XLA route, ``"1"`` =
    kernels forced (CPU tests; interpreted there)."""
    key = "BYZPY_TPU_PALLAS"
    saved = os.environ.get(key)
    if value is None:
        os.environ.pop(key, None)
    else:
        os.environ[key] = value
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = saved


def _separable_rows(n: int, d: int, dtype, seed: int) -> jax.Array:
    """``(n, d)`` rows around a common mean with a distinct noise scale
    per row (1.0 … 2.0), so every score-ranking aggregator has gaps of
    percents between consecutive scores: the kernel and XLA routes must
    then SELECT the same rows, and what the tolerance covers is only how
    each sums them."""
    k_mu, k_noise = jax.random.split(jax.random.PRNGKey(seed))
    mu = jax.random.normal(k_mu, (1, d), jnp.float32)
    scale = (1.0 + jnp.arange(n, dtype=jnp.float32) / n)[:, None]
    noise = jax.random.normal(k_noise, (n, d), jnp.float32)
    return (mu + scale * noise).astype(dtype)


def _kernel_cases(n: int, d: int, quant_pallas: Optional[bool]) -> List[tuple]:
    """``(case, kernel entry point, fn(x), tolerance class)`` for every
    Pallas entry point the default dispatch reaches (plus ``gram_pallas``
    and ``sort_columns`` called directly), then the opt-in kernels that
    compile and agree on the chip (PR 21: the ragged segment sums and the
    fp8 codecs; the s4 kernels do not lower — ROADMAP S4). ``fn`` goes
    through the PUBLIC dispatching function where there is one, so the
    same callable serves both routes."""
    from byzpy_tpu.ops import attack_ops, coordinatewise, preagg, robust
    from byzpy_tpu.ops import pallas_kernels as pk
    from byzpy_tpu.parallel.quantization import (
        dequantize_blockwise,
        encode_blockwise,
    )

    f = max(1, n // 8)  # BASELINE's 64-row shape runs f=8, q=12
    q = max(2, (3 * n) // 16)
    # row norms are ~1.4-2.2 sqrt(d): thresholds that clip every row by a
    # factor of O(1), so outputs stay O(1) like the tolerances assume
    tau = float(d ** 0.5)
    c_tau = float(0.1 * d ** 0.5)

    def xla_route() -> bool:
        return os.environ.get("BYZPY_TPU_PALLAS") == "0"

    flip = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")

    def attacked(x):
        # the last f workers byzantine: the kernel forms their rows from the
        # others' in its body (the streamed round's call); XLA writes them
        honest = x[: n - f]
        if xla_route():
            rows = jnp.broadcast_to(flip(honest, None).astype(x.dtype), (f, d))
            return robust.trimmed_mean(jnp.concatenate([honest, rows]), f=f)
        return robust.trimmed_mean_attacked(honest, f=f, attack=flip, b=f)

    def from_gram(x):
        with jax.default_matmul_precision("highest"):
            gram = robust.gram_matrix(x)
        return robust.multi_krum_from_gram(x, gram, f=f, q=q)

    def stream2(x):
        return robust.multi_krum_stream(
            jnp.stack([x, x * jnp.asarray(0.5, x.dtype)]), f=f, q=q
        )

    def sort(x):
        return jnp.sort(x, axis=0) if xla_route() else pk.sort_columns(x)

    def codec(x, mode):
        # int8 kernels are the TPU default; the fp8 kernels are opt-in
        # (BYZPY_TPU_SUBINT8_PALLAS) and asked for explicitly here
        kernel = quant_pallas if mode == "int8" else True
        use = False if xla_route() else kernel
        qb = encode_blockwise(x, mode, use_pallas=use)
        deq = dequantize_blockwise(qb, dtype=jnp.float32, use_pallas=use)
        return _code_bits(qb.values), qb.scales, deq

    # three cohorts of a flat ragged batch, reciprocal sizes baked in
    bounds = [0, n // 3, (2 * n) // 3, n]
    weights = np.zeros((3, n), np.float32)
    for c in range(3):
        weights[c, bounds[c]:bounds[c + 1]] = 1.0 / (bounds[c + 1] - bounds[c])
    weights = jnp.asarray(weights)

    def ragged_sum(x):
        if xla_route():
            return jnp.einsum(
                "cr,rd->cd", weights, x.astype(jnp.float32)
            ).astype(x.dtype)
        return pk.ragged_segment_sum_pallas(x, weights)

    def ragged_dequant(x, mode):
        qb = encode_blockwise(x, mode, use_pallas=False)
        if xla_route():
            return jnp.einsum(
                "cr,rd->cd", weights,
                dequantize_blockwise(qb, dtype=jnp.float32, use_pallas=False),
            )
        return pk.ragged_segment_sum_dequant_pallas(
            _code_bits(qb.values), qb.scales, weights, mode=mode,
            block=qb.block, d=d,
        )

    return [
        ("coordinate_median", "sorted_reduce_stream_pallas",
         robust.coordinate_median, "select"),
        ("trimmed_mean", "sorted_reduce_stream_pallas",
         partial(robust.trimmed_mean, f=f), "mean"),
        ("trimmed_mean_attacked", "sorted_reduce_stream_pallas[attack]", attacked, "mean"),
        ("mean_of_medians", "meamed_stream_pallas | sort_columns",
         partial(robust.mean_of_medians, f=f), "mean"),
        ("multi_krum", "selection_mean_pallas",
         partial(robust.multi_krum, f=f, q=q), "mean"),
        ("multi_krum_stream", "selection_mean_stream_pallas", stream2, "mean"),
        ("cge", "selection_mean_pallas[cge]", partial(robust.cge, f=f), "mean"),
        ("monna", "selection_mean_pallas[monna]",
         partial(robust.monna, f=f), "mean"),
        ("multi_krum_from_gram", "selection_mean_from_gram_pallas",
         from_gram, "mean"),
        ("nnm", "nnm_pallas / nnm_stream_pallas",
         partial(preagg.nnm, f=f), "mean"),
        ("nnm_multi_krum", "nnm_selection_mean_stream_pallas",
         partial(robust.nnm_multi_krum, f_nnm=f, f=f, q=q), "mean"),
        ("clipped_multi_krum", "clip_selection_mean_stream_pallas",
         partial(robust.clipped_multi_krum, tau=tau, f=f, q=q), "mean"),
        ("arc_multi_krum", "arc_selection_mean_stream_pallas",
         partial(robust.arc_multi_krum, f_arc=f, f=f, q=q), "mean"),
        ("geometric_median", "weighted_center_step_pallas[weiszfeld]",
         partial(robust.geometric_median, tol=0.0, max_iter=4), "iterate"),
        ("centered_clipping", "weighted_center_step_pallas[clip]",
         partial(robust.centered_clipping, c_tau=c_tau, M=3), "iterate"),
        # its reference is the float64 Gram computed on the host
        ("gram", "gram_pallas", pk.gram_pallas, "gram"),
        ("sort_columns", "sort_columns", sort, "select"),
        ("int8_codec", "quantize/dequantize_blockwise kernels",
         partial(codec, mode="int8"), "codec:int8"),
        ("fp8_codec", "encode/dequantize_blockwise fp8 kernels (opt-in)",
         partial(codec, mode="fp8"), "codec:fp8"),
        ("fp8_e5m2_codec", "encode/dequantize_blockwise e5m2 kernels (opt-in)",
         partial(codec, mode="fp8_e5m2"), "codec:fp8_e5m2"),
        ("ragged_segment_sum", "ragged_segment_sum_pallas (opt-in)",
         ragged_sum, "mean"),
        ("ragged_dequant_int8", "ragged_segment_sum_dequant_pallas (opt-in)",
         partial(ragged_dequant, mode="int8"), "mean32"),
        ("ragged_dequant_fp8", "ragged_segment_sum_dequant_pallas (opt-in)",
         partial(ragged_dequant, mode="fp8"), "mean32"),
    ]


def _code_bits(values: jax.Array) -> jax.Array:
    """Wire codes as integers (fp8 values as their uint8 bit patterns)."""
    if values.dtype in (jnp.int8, jnp.uint8):
        return values
    return jax.lax.bitcast_convert_type(values, jnp.uint8)


def _tolerance(kind: str, dtype) -> Tuple[float, float]:
    """``(rtol, atol)`` between the kernel route and the XLA route.

    Inputs are O(1) (|x| < ~10). Both routes select the same rows (see
    ``_separable_rows``) and accumulate in f32; they differ in summation
    order and, for 16-bit inputs, in one final rounding to the input
    dtype.

    * ``select`` (median, sort): outputs are input elements or the
      midpoint of two — exact in f32; 16-bit: one rounding of the midpoint.
    * ``mean`` (trimmed/selected/mixed means of <= 64 rows): <= 64 f32
      roundings of O(1) partial sums, 64 * 2^-24 * 10 ~ 4e-5.
    * ``iterate`` (Weiszfeld / centered-clipping steps): each step's row
      weights come from sqrt of a d-term f32 sum whose order differs
      (relative ~1e-6 * a few), and 3-4 steps compound it.
    * ``gram`` (against the float64 Gram computed on the host): the
      kernel multiplies on the MXU at its default precision, which rounds
      f32 multiplicands to bf16 (2^-9 relative each), so every product is
      within 2^-8 of exact and |dG_ij| <= 2^-8 sqrt(G_ii G_jj) <= 2^-8
      max|G|; f32 accumulation of the tile sums is orders below that.
      Stated relative to max|G|. (XLA's own f32 einsum at the TPU's
      default precision sits at the same distance.) Distances only
      perturb score near-ties, which ``_separable_rows`` keeps percents
      away.
    * 16-bit inputs: outputs are rounded to the input dtype, one ulp is
      2^-8 (bf16) relative.
    """
    sixteen = jnp.dtype(dtype).itemsize == 2 and kind != "mean32"
    if kind == "gram":
        return 0.0, 2.0 ** -8
    if sixteen and kind == "iterate":
        # the kernel stores the center in the input dtype between steps:
        # up to one ulp per step over 3-4 steps, where the reference
        # rounds once at the end
        return 2.0 ** -5, 2.0 ** -5
    if sixteen:
        return 2.0 ** -7, 2.0 ** -7
    if kind == "select":
        return 0.0, 0.0
    if kind in ("mean", "mean32"):  # mean32: f32 out whatever the input
        return 1e-5, 4e-5
    if kind == "iterate":
        return 1e-4, 1e-4
    raise ValueError(kind)


def _to_host(out: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a.astype(jnp.float32))
        if a.dtype in (jnp.bfloat16, jnp.float16) else np.asarray(a),
        out,
    )


def _block_absmax(x_host: np.ndarray) -> np.ndarray:
    """Per value, the absmax of its quantization block (what the codecs'
    error bounds scale with); once per shape, shared by the codec cases."""
    from byzpy_tpu.parallel.quantization import DEFAULT_BLOCK

    n, width = x_host.shape
    blocks = -(-width // DEFAULT_BLOCK)
    padded = np.zeros((n, blocks * DEFAULT_BLOCK), np.float32)
    padded[:, :width] = np.abs(x_host)
    absmax = padded.reshape(n, blocks, DEFAULT_BLOCK).max(axis=2)
    return np.repeat(absmax, DEFAULT_BLOCK, axis=1)[:, :width]


def _compare_case(kind: str, dtype, got: Any, ref: Any,
                  x_host: np.ndarray, block_absmax: np.ndarray) -> Dict[str, Any]:
    if kind.startswith("codec:"):
        from byzpy_tpu.parallel.quantization import CommPrecision

        codes, scales, deq = got
        rcodes, rscales, rdeq = ref
        # the codec's own contract (CommPrecision.error_bound): every
        # value within the mode's bound for its block's absmax; and the
        # two routes at most one code apart (a rounding tie may break
        # either way after a 1-ulp difference in the reciprocal scale;
        # XLA's f32->f8 convert double-rounds through f16, Mosaic's
        # does not)
        bound = CommPrecision(kind.split(":")[1]).error_bound(1.0) * block_absmax
        err = float(np.max(np.abs(deq - x_host) / np.maximum(bound, 1e-30)))
        _require(err <= 1.0 + 1e-3, f"dequantized error {err} x the bound")
        code_gap = int(np.max(np.abs(
            codes.astype(np.int32) - rcodes.astype(np.int32)
        )))
        _require(code_gap <= 1, f"codes differ by {code_gap}")
        np.testing.assert_allclose(scales, rscales, rtol=1e-6, atol=0)
        return {
            "max_err": round(err, 4),
            "code_gap": code_gap,
            "bit_equal": bool(
                np.array_equal(codes, rcodes) and np.array_equal(deq, rdeq)
            ),
        }
    rtol, atol = _tolerance(kind, dtype)
    if kind == "gram":
        atol *= float(np.max(np.abs(ref)))
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    _require(got.shape == ref.shape, f"shape {got.shape} vs {ref.shape}")
    _require(bool(np.isfinite(got).all()), "non-finite kernel output")
    err = float(np.max(np.abs(got - ref)))
    bound = atol + rtol * float(np.max(np.abs(ref)))
    _require(
        bool(np.all(np.abs(got - ref) <= atol + rtol * np.abs(ref))),
        f"max |kernel - xla| = {err:.3e} exceeds rtol={rtol:g} atol={atol:g}",
    )
    return {"max_err": float(f"{err:.3e}"), "bound": float(f"{bound:.3e}"),
            "bit_equal": bool(np.array_equal(got, ref))}


def phase_kernels(
    shapes: Sequence[Tuple[int, int, str]],
    *,
    kernel_route: Optional[str] = None,
    require_mosaic: bool = True,
) -> Dict[str, Any]:
    """Every default-dispatch Pallas entry point at each ``(n, d, dtype)``
    against the XLA implementation of the same aggregate.
    ``kernel_route`` is the ``BYZPY_TPU_PALLAS`` value of the kernel pass:
    ``None`` on the chip (the default dispatch must reach the kernel by
    itself), ``"1"`` in the CPU tests (forced, interpreted).
    ``require_mosaic`` asserts the compiled kernel route contains the
    Mosaic custom call — the proof that ``interpret=False`` ran."""
    results: Dict[str, Any] = {}
    for n, d, dtype_name in shapes:
        dtype = jnp.dtype(dtype_name)
        x = _separable_rows(n, d, dtype, seed=n + d)
        jax.block_until_ready(x)
        x_host = np.asarray(x.astype(jnp.float32))
        block_absmax = _block_absmax(x_host)
        cases = _kernel_cases(
            n, d, quant_pallas=None if kernel_route is None else True
        )
        kernel_out: Dict[str, Any] = {}
        with _pallas_dispatch(kernel_route):
            for case, entry, fn, _kind in cases:
                compiled = jax.jit(fn).lower(x).compile()
                if require_mosaic:
                    _require(
                        _MOSAIC_MARK in compiled.as_text(),
                        f"{case} at {n}x{d} {dtype_name}: no Mosaic custom "
                        f"call in the compiled program ({entry} did not run "
                        f"as a compiled kernel)",
                    )
                kernel_out[case] = _to_host(compiled(x))
        # some dispatches resolve inside an inner jit (ops.preagg.nnm):
        # drop every trace before switching routes
        jax.clear_caches()
        # The kernels up-cast 16-bit inputs and compute in f32 (the XLA
        # route computes some aggregates in the input dtype, where ties
        # between 8-bit mantissas select other rows): the reference for a
        # 16-bit input is the XLA aggregate of the same values in f32,
        # rounded once to the input dtype.
        x_ref = x.astype(jnp.float32) if dtype.itemsize == 2 else x
        with _pallas_dispatch("0"), jax.default_matmul_precision("highest"):
            for case, entry, fn, kind in cases:
                if kind == "gram":
                    x64 = x_host.astype(np.float64)
                    ref = x64 @ x64.T
                    del x64
                else:
                    compiled = jax.jit(fn).lower(x_ref).compile()
                    _require(
                        _MOSAIC_MARK not in compiled.as_text(),
                        f"{case}: the XLA reference route contains a kernel",
                    )
                    ref = compiled(x_ref)
                    if not kind.startswith(("codec", "mean32")):
                        ref = ref.astype(dtype)
                    ref = _to_host(ref)
                try:
                    results[f"{case}@{n}x{d}:{dtype_name}"] = _compare_case(
                        kind, dtype, kernel_out.pop(case), ref, x_host,
                        block_absmax,
                    )
                except AssertionError as exc:
                    raise SmokeFailure(
                        f"{case} ({entry}) at {n}x{d} {dtype_name}: {exc}"
                    ) from exc
        jax.clear_caches()
    return {
        "cases": results,
        "asserted": [
            "Mosaic custom call in every kernel-route program"
            if require_mosaic else "kernel route forced (interpreted)",
            "no kernel in the XLA route",
            "kernel == XLA within _tolerance()",
        ],
    }


# ---------------------------------------------------------------------------
# Phase 3 / 5: the fused robust PS round
# ---------------------------------------------------------------------------


def _sign_flip_attack(honest, key):
    from byzpy_tpu.ops import attack_ops

    return attack_ops.sign_flip(jnp.mean(honest, axis=0))


def _empire_attack(honest, key):
    from byzpy_tpu.ops import attack_ops

    return attack_ops.empire(honest)


def _platforms_of(tree: Any) -> set:
    return {
        dev.platform
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
        for dev in leaf.devices()
    }


def _train(
    make_bundle: Callable[[], Any],
    aggregate: Callable,
    attack: Callable,
    *,
    input_shape: Tuple[int, ...],
    n_nodes: int,
    n_byzantine: int,
    batch: int,
    steps: int,
    learning_rate: float,
    mesh: Any,
    expect_kernel: Optional[bool],
    platform: str,
) -> Dict[str, Any]:
    """``steps`` fused rounds on one fixed batch per node; returns the
    loss sequence and what was asserted on the way."""
    from byzpy_tpu.models import synthetic_classification
    from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step

    bundle = make_bundle()
    x, y = synthetic_classification(
        n_samples=n_nodes * batch, input_shape=input_shape, seed=3
    )
    xs = x.reshape(n_nodes, batch, *input_shape)
    ys = y.reshape(n_nodes, batch)
    if mesh is not None:
        from byzpy_tpu.parallel.mesh import node_axis, sharding

        node_sharding = sharding(mesh, node_axis(mesh))
        xs = jax.device_put(xs, node_sharding)
        ys = jax.device_put(ys, node_sharding)
    cfg = PSStepConfig(
        n_nodes=n_nodes, n_byzantine=n_byzantine, learning_rate=learning_rate
    )
    step, opt_state = jit_ps_train_step(
        bundle, aggregate, cfg, attack=attack, mesh=mesh, donate=True
    )
    # the step donates params and optimizer state: train on a copy, the
    # bundle's arrays stay valid for the next run
    params = jax.tree_util.tree_map(jnp.copy, bundle.params)
    if mesh is not None:
        # the step returns params replicated over the mesh; fed params
        # that are not, its second call would compile a second program
        from byzpy_tpu.parallel.mesh import replicated

        params = jax.device_put(params, replicated(mesh))
    d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    key = jax.random.PRNGKey(0)
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (params, opt_state, xs, ys, key),
    )
    losses = []
    metrics = None
    for i in range(steps):
        params, opt_state, metrics = step(
            params, opt_state, xs, ys, jax.random.fold_in(key, i)
        )
        losses.append(float(metrics["honest_loss"]))
    jax.block_until_ready((params, opt_state))
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(
        losses[-1] < losses[0],
        f"loss did not fall over {steps} steps: {losses}",
    )
    _require(
        step._cache_size() == 1,
        f"{step._cache_size()} compilations of one step function",
    )
    where = _platforms_of((params, opt_state, metrics))
    _require(where == {platform}, f"state lives on {where}, not {platform}")
    # the executable the loop ran, again through the AOT door (a
    # persistent-cache hit), for its text
    compiled = step.lower(*abstract).compile()
    text = compiled.as_text()
    if expect_kernel is not None:
        _require(
            (_MOSAIC_MARK in text) == expect_kernel,
            f"compiled step {'lacks' if expect_kernel else 'contains'} the "
            f"Pallas custom call",
        )
    return {
        "d": int(d),
        "losses": [round(v, 5) for v in losses],
        "pallas_in_step": _MOSAIC_MARK in text,
        "compilations": step._cache_size(),
        "_state": (params, opt_state, xs),
        "_compiled_text": text,
    }


def _public(run: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in run.items() if not k.startswith("_")}


def _robust_rounds(n_byzantine: int, q: int) -> List[tuple]:
    from byzpy_tpu.ops import robust

    return [
        ("trimmed_mean/sign_flip",
         partial(robust.trimmed_mean, f=n_byzantine), _sign_flip_attack),
        ("multi_krum/empire",
         partial(robust.multi_krum, f=n_byzantine, q=q), _empire_attack),
    ]


def phase_trainer(
    make_bundle: Callable[[], Any],
    *,
    input_shape: Tuple[int, ...],
    n_nodes: int = 8,
    n_byzantine: int = 2,
    q: int = 4,
    batch: int = 32,
    steps: int = 5,
    learning_rate: float = 0.005,
    expect_kernel: Optional[bool] = True,
    baseline3: bool = True,
    platform: str = "tpu",
) -> Dict[str, Any]:
    """One chip: ``jit_ps_train_step`` (donation on) under two
    aggregator/attack pairs, then BASELINE config #3 (``mnist_mlp``,
    trimmed mean, sign-flip; d below ``MIN_PALLAS_DIM``, so the XLA
    route) when ``baseline3``. ``learning_rate``: ResNet-18 from a
    random init diverges within five steps at ``PSStepConfig``'s default
    0.05 (loss 2.7 -> 17 on the CPU); 0.005 falls."""
    from byzpy_tpu.models import mnist_mlp
    from byzpy_tpu.ops import robust

    # parallel.ps resolves mesh=None to the default mesh, which nobody
    # set: whatever the host has, this phase runs on the first device
    out: Dict[str, Any] = {
        "mesh": f"none: device {jax.devices()[0].id} of {len(jax.devices())}",
        "runs": {},
    }
    for name, aggregate, attack in _robust_rounds(n_byzantine, q):
        out["runs"][name] = _public(_train(
            make_bundle, aggregate, attack, input_shape=input_shape,
            n_nodes=n_nodes, n_byzantine=n_byzantine, batch=batch,
            steps=steps, learning_rate=learning_rate, mesh=None,
            expect_kernel=expect_kernel,
            platform=platform,
        ))
    if baseline3:
        out["runs"]["baseline3:mnist_mlp/trimmed_mean/sign_flip"] = _public(
            _train(
                mnist_mlp, partial(robust.trimmed_mean, f=2),
                _sign_flip_attack, input_shape=(28, 28, 1), n_nodes=8,
                n_byzantine=2, batch=64, steps=steps, learning_rate=0.05,
                mesh=None,
                expect_kernel=False, platform=platform,
            )
        )
    out["asserted"] = [
        "loss finite and lower at the last step than at the first",
        f"params, optimizer state and metrics on {platform}",
        "Pallas custom call in the compiled step" if expect_kernel
        else "aggregate route not asserted",
        "no Pallas call in the BASELINE #3 step (XLA route)",
        "exactly one compilation per step function",
    ]
    return out


def phase_mesh_trainer(
    make_bundle: Callable[[], Any],
    single_chip_losses: Dict[str, List[float]],
    *,
    input_shape: Tuple[int, ...],
    n_chips: int = 4,
    n_nodes: int = 8,
    n_byzantine: int = 2,
    q: int = 4,
    batch: int = 32,
    steps: int = 5,
    learning_rate: float = 0.005,
    platform: str = "tpu",
    loss_rtol: float = 1e-2,
) -> Dict[str, Any]:
    """Phase 3's rounds over ``node_mesh(n_chips)``: placement on every
    chip, the collective law, no whole-matrix collective, and the loss
    sequence of the one-chip run.

    ``loss_rtol``: the mesh step aggregates on the XLA route and runs
    each chip's two workers through convolutions of another batch shape
    than the one-chip step (1e-6-class differences per coordinate), and
    five SGD+momentum steps amplify them — 1.3e-3 on the loss at most on
    four v5e chips (PR 21). 1 % is far above that and far below what a
    wrong aggregate (an attack getting through) does to it."""
    from byzpy_tpu.parallel.comms import collectives_in_hlo
    from byzpy_tpu.parallel.mesh import node_mesh

    mesh = node_mesh(n_chips)
    g = n_chips
    out: Dict[str, Any] = {
        "mesh": f"node_mesh({n_chips}): axes {dict(mesh.shape)}", "runs": {},
    }
    for name, aggregate, attack in _robust_rounds(n_byzantine, q):
        run = _train(
            make_bundle, aggregate, attack, input_shape=input_shape,
            n_nodes=n_nodes, n_byzantine=n_byzantine, batch=batch,
            steps=steps, learning_rate=learning_rate, mesh=mesh,
            expect_kernel=False, platform=platform,
        )
        params, opt_state, xs = run["_state"]
        d = run["d"]
        # placement: xs by node rows, the flat optimizer state by feature
        # columns, on every chip
        n_dev = {len({s.device for s in xs.addressable_shards})}
        flat_leaves = [
            leaf for leaf in jax.tree_util.tree_leaves(opt_state)
            if getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] >= d
        ]
        _require(bool(flat_leaves), "no flat optimizer state found")
        for leaf in flat_leaves:
            n_dev.add(len({s.device for s in leaf.addressable_shards}))
            _require(
                leaf.addressable_shards[0].data.shape[0] * g == leaf.shape[0],
                f"optimizer state not sharded {g} ways: "
                f"{leaf.addressable_shards[0].data.shape} of {leaf.shape}",
            )
        _require(n_dev == {g}, f"data occupies {n_dev} chips, not {g}")
        d_pad = flat_leaves[0].shape[0]
        # collectives of the compiled step, per device: the law is
        # d·4·(g−1)/g for the params all-gather, and n/g times that for
        # the gradient transpose (n/g rows per chip)
        ops = collectives_in_hlo(run["_compiled_text"], default_group=g)
        per: Dict[str, int] = {}
        for op in ops:
            per[op.opcode] = per.get(op.opcode, 0) + op.wire_bytes_per_device
        gather_law = d_pad * 4 * (g - 1) / g
        transpose_law = (n_nodes / g) * gather_law
        biggest = max((op.result_bytes for op in ops), default=0)
        whole = n_nodes * d * 4
        _require(
            biggest < 0.6 * whole,
            f"a collective moves {biggest} bytes; the whole (n, d) matrix "
            f"is {whole}: per-opcode {per}",
        )
        _require(
            abs(per.get("all-to-all", 0) - transpose_law) < 0.05 * transpose_law,
            f"all-to-all {per.get('all-to-all', 0)} B/device vs law "
            f"{transpose_law:.0f}: {per}",
        )
        # the refreshed params go back to every chip ONCE. XLA:CPU emits
        # the all-gather the sharded update asks for, at the law;
        # XLA:TPU (jax 0.9.0) lowers it to dynamic-update-slice + an
        # all-reduce of the whole d-vector, which a ring moves at twice
        # the law. Either is one d-sized collective — say which.
        gathered = per.get("all-gather", 0)
        reduced = sum(
            op.wire_bytes_per_device for op in ops
            if op.opcode == "all-reduce" and op.result_bytes >= d_pad * 4
        )
        if abs(gathered - gather_law) < 0.05 * gather_law:
            gather_as = "all-gather"
        elif not gathered and abs(reduced - 2 * gather_law) < 0.05 * gather_law:
            gather_as = "all-reduce(dynamic-update-slice): 2x the law"
        else:
            raise SmokeFailure(
                f"params gather: all-gather {gathered} B/device, d-sized "
                f"all-reduce {reduced} B/device, law {gather_law:.0f}: {per}"
            )
        ref = single_chip_losses[name]
        np.testing.assert_allclose(run["losses"], ref, rtol=loss_rtol)
        run = _public(run)
        run.update(
            per_opcode_bytes=per, params_gather_lowered_as=gather_as,
            gather_law_bytes=int(gather_law), largest_collective_bytes=biggest,
            whole_matrix_bytes=whole, one_chip_losses=ref,
        )
        out["runs"][name] = run
    out["asserted"] = [
        f"xs and the flat optimizer state occupy all {g} chips",
        "all-to-all bytes per device within 5% of the law; the params "
        "gather one d-sized collective (all-gather at the law, or XLA:TPU's "
        "all-reduce form at twice it)",
        "no collective result >= 0.6 of the (n, d) matrix",
        f"losses within {loss_rtol:g} (relative) of the one-chip run",
        "one compilation per step function, no Pallas call on sharded operands",
    ]
    return out


# ---------------------------------------------------------------------------
# Phase 4: serving
# ---------------------------------------------------------------------------


def phase_serving(
    make_bundle: Callable[[], Any],
    *,
    dim: int = 1 << 20,
    clients: int = 64,
    cohorts: Sequence[int] = (64, 37, 50),
    step_capacity: int = 16,
    step_cohort: int = 11,
    platform: str = "tpu",
) -> Dict[str, Any]:
    """Three rounds through one in-process ``ServingFrontend`` (submit →
    close) for a trimmed-mean tenant — three cohort sizes, one compiled
    program; one ``jit_ragged_serving_ps_step`` call on ``make_bundle``'s
    model; one donated masked finalize.

    Tolerance: the ragged and masked programs run the XLA sort + a
    zero-padded einsum window; the direct aggregator on the chip runs
    the fused kernel. Both average the same <= 60 sorted values per
    coordinate in f32 in different orders: the ``mean`` class of
    ``_tolerance``."""
    from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
    from byzpy_tpu.parallel.ps import jit_ragged_serving_ps_step
    from byzpy_tpu.serving.frontend import ServingFrontend, TenantConfig

    rtol, atol = _tolerance("mean", jnp.float32)
    agg = CoordinateWiseTrimmedMean(f=2)
    frontend = ServingFrontend([
        TenantConfig(name="smoke", aggregator=agg, dim=dim,
                     cohort_cap=max(cohorts)),
    ])
    rng = np.random.default_rng(0)
    base = rng.normal(size=(clients, dim)).astype(np.float32)
    rounds = []
    for r, m in enumerate(cohorts):
        rows = base[:m] * np.float32(1.0 + r)
        for c in range(m):
            accepted, reason = frontend.submit(
                "smoke", f"client-{c}", frontend.round_of("smoke"), rows[c],
                seq=r,
            )
            _require(accepted, f"submission rejected: {reason}")
        closed = frontend.close_round_nowait("smoke")
        _require(
            closed is not None,
            f"round {r} (m={m}) did not close: {frontend.stats()}",
        )
        _rid, cohort, vec = closed
        _require(cohort.m == m, f"cohort of {cohort.m}, expected {m}")
        direct = np.asarray(agg.aggregate(list(jnp.asarray(rows))))
        got = np.asarray(vec)
        np.testing.assert_allclose(got, direct, rtol=rtol, atol=atol)
        rounds.append({
            "m": m, "max_err": float(f"{np.max(np.abs(got - direct)):.3e}"),
            "bit_equal": bool(np.array_equal(got, direct)),
        })
    stats = frontend.stats()["smoke"]
    _require(stats["failed_rounds"] == 0, f"failed rounds: {stats}")
    _require(stats["ragged_served"], "the tenant left the ragged door")
    ragged = stats["frontend"]["ragged"]
    _require(
        ragged["compile_entries"] == 1 and ragged["dispatches"] == len(cohorts),
        f"{len(cohorts)} cohort sizes took {ragged['compile_entries']} "
        f"programs / {ragged['dispatches']} dispatches",
    )

    # the ragged serving update step, donated, on the model
    bundle = make_bundle()
    params = jax.tree_util.tree_map(jnp.copy, bundle.params)
    d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    step, opt_state = jit_ragged_serving_ps_step(
        bundle, agg.ragged_matrix_fn(), row_capacity=step_capacity,
        donate=True,
    )
    flat = np.zeros((step_capacity, d), np.float32)
    flat[:step_cohort] = rng.normal(size=(step_cohort, d)).astype(np.float32)
    weights = np.zeros((step_capacity,), np.float32)
    weights[:step_cohort] = 1.0
    params, opt_state, metrics = step(
        params, opt_state, flat, np.zeros(1, np.int32),
        np.asarray([step_cohort], np.int32), weights,
    )
    jax.block_until_ready(params)
    _require(int(metrics["cohort_m"]) == step_cohort, f"metrics {metrics}")
    direct_norm = float(jnp.linalg.norm(
        agg.aggregate(list(jnp.asarray(flat[:step_cohort])))
    ))
    np.testing.assert_allclose(
        float(metrics["agg_grad_norm"]), direct_norm, rtol=1e-4
    )
    where = _platforms_of((params, opt_state, metrics))
    _require(where == {platform}, f"serving step state on {where}")

    # the donated masked finalize (root close path): dispatched, returned
    # unmaterialized on the device
    m = cohorts[1]
    rows = base[:m]
    out = agg.fold_merge_finalize(
        {"rows": rows}, bucket=max(cohorts), donate=True
    )
    _require(isinstance(out, jax.Array), f"finalize returned {type(out)}")
    _require(_platforms_of(out) == {platform}, "finalize left the device")
    direct = np.asarray(agg.aggregate(list(jnp.asarray(rows))))
    np.testing.assert_allclose(np.asarray(out), direct, rtol=rtol, atol=atol)
    return {
        "frontend_rounds": rounds,
        "ragged_compile_entries": ragged["compile_entries"],
        "serving_step_d": int(d),
        "donated_finalize_bit_equal": bool(np.array_equal(np.asarray(out), direct)),
        "asserted": [
            "three cohort sizes closed through the ragged door by one "
            "compiled program, no failed round",
            "each aggregate == the direct aggregator within the mean tolerance",
            f"ragged PS step state and donated finalize output on {platform}",
        ],
    }


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main() -> int:
    try:
        devices = tpu_devices()
    except SmokeFailure as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 1

    from byzpy_tpu.models import cifar_resnet18
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    resnet_d = sum(
        leaf.size
        for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: cifar_resnet18(seed=0).params)
        )
    )
    make_resnet = partial(cifar_resnet18, seed=0)
    records = [run_phase("device", phase_device)]
    records.append(run_phase("kernels", lambda: phase_kernels([
        (64, 1 << 20, "float32"),
        (64, 1 << 20, "bfloat16"),
        (8, resnet_d, "float32"),
    ])))
    trainer = run_phase("trainer", lambda: phase_trainer(
        make_resnet, input_shape=(32, 32, 3),
    ))
    records.append(trainer)
    records.append(run_phase("serving", lambda: phase_serving(make_resnet)))
    if len(devices) >= 4:
        losses = {
            name: run["losses"] for name, run in trainer["runs"].items()
            if not name.startswith("baseline3")
        }
        records.append(run_phase("mesh", lambda: phase_mesh_trainer(
            make_resnet, losses, input_shape=(32, 32, 3),
        )))
    else:
        print(f"mesh phase not run: {len(devices)} device(s)", flush=True)
    print(json.dumps({
        "ok": all(r["ok"] for r in records),
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as exc:  # the phase line already names it
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(exc).__name__}", file=sys.stderr)
        sys.exit(1)
