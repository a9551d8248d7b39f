"""byzpy-tpu command-line interface.

API parity: ``byzpy/cli.py:122-164`` — subcommands ``version``, ``doctor``
(environment report; the reference probes torch/CUDA/cupy/UCX at
cli.py:38-74, here we probe the JAX platform, device inventory, and
native-extension availability), and ``list aggregators|attacks|
pre-aggregators`` via subclass discovery (ref: cli.py:14-35).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Type

from .version import __version__


def _subclasses_of(base: Type) -> List[Type]:
    """All concrete registered subclasses, sorted by name (the package
    __init__ imports every built-in, so walking the subclass tree is the
    same discovery the reference does by scanning packages)."""
    seen: Dict[str, Type] = {}
    stack = list(base.__subclasses__())
    while stack:
        cls = stack.pop()
        stack.extend(cls.__subclasses__())
        if not getattr(cls, "__abstractmethods__", None):
            seen[cls.__name__] = cls
    return [seen[k] for k in sorted(seen)]


def _collect(kind: str) -> List[Type]:
    if kind == "aggregators":
        import byzpy_tpu.aggregators as pkg
        from byzpy_tpu.aggregators.base import Aggregator as base
    elif kind == "attacks":
        import byzpy_tpu.attacks as pkg  # noqa: F401 — import registers subclasses
        from byzpy_tpu.attacks.base import Attack as base
    elif kind == "pre-aggregators":
        import byzpy_tpu.pre_aggregators as pkg  # noqa: F401
        from byzpy_tpu.pre_aggregators.base import PreAggregator as base
    else:  # pragma: no cover - argparse choices guard this
        raise ValueError(kind)
    return _subclasses_of(base)


def cmd_version(_args: argparse.Namespace) -> int:
    """``byzpy-tpu version``: print the package version."""
    print(__version__)
    return 0


def doctor_report() -> Dict[str, Any]:
    """Environment probe (ref: ``byzpy doctor``, cli.py:38-74)."""
    report: Dict[str, Any] = {"version": __version__, "python": sys.version.split()[0]}
    try:
        import jax

        report["jax"] = {"version": jax.__version__, "ok": True}
        try:
            devices = jax.devices()
        except RuntimeError as exc:  # backend failed to initialize
            report["devices_error"] = repr(exc)
        else:
            report["devices"] = [
                {
                    "id": d.id,
                    "platform": d.platform,
                    "kind": d.device_kind,
                    "process": d.process_index,
                }
                for d in devices
            ]
            report["default_backend"] = jax.default_backend()
            report["device_kind"] = devices[0].device_kind
            report["device_count"] = len(devices)
            report["process_count"] = jax.process_count()
    except Exception as exc:  # noqa: BLE001
        report["jax"] = {"ok": False, "error": repr(exc)}
    for mod in ("flax", "optax", "cloudpickle"):
        try:
            m = __import__(mod)
            report[mod] = {"ok": True, "version": getattr(m, "__version__", "?")}
        except Exception as exc:  # noqa: BLE001
            report[mod] = {"ok": False, "error": repr(exc)}
    try:
        from .engine.storage import native_store

        report["native_shm_store"] = {"ok": native_store.available()}
    except Exception:  # noqa: BLE001 — optional native extension
        report["native_shm_store"] = {"ok": False}
    return report


def cmd_doctor(args: argparse.Namespace) -> int:
    """``byzpy-tpu doctor``: print the environment probe (text or json)."""
    report = doctor_report()
    if args.format == "json":
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for key, value in sorted(report.items()):
            print(f"{key}: {value}")
    return 0


def cmd_list(args: argparse.Namespace) -> int:
    """``byzpy-tpu list``: enumerate registered aggregators/attacks/pre-aggregators."""
    for cls in _collect(args.kind):
        name = getattr(cls, "name", None) or cls.__name__
        print(f"{cls.__name__}\t({name})")
    return 0


def bench_report(*, n: int = 16, d: int = 65_536, repeat: int = 10) -> Dict[str, Any]:
    """Quick on-device micro-benchmark of the hot aggregators (one JSON
    row per op, milliseconds per call) — the sanity companion to
    ``doctor``: is this device delivering the expected order of
    magnitude? The report names the device it ran on; a device or
    compile failure raises (no ``{"error": ...}`` with exit 0)."""
    import jax
    import jax.numpy as jnp

    from .ops import robust
    from .observability.compat import timed_call_s

    devices = jax.devices()
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    rows: Dict[str, Any] = {
        "device": str(devices[0]),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "shape": [n, d],
        "repeat": repeat,
    }
    from functools import partial

    f = max(1, n // 8)
    ops = {
        "coordinate_median": robust.coordinate_median,
        "trimmed_mean": partial(robust.trimmed_mean, f=f),
        "multi_krum": partial(robust.multi_krum, f=f, q=max(1, n // 4)),
        "geometric_median": partial(robust.geometric_median, max_iter=32),
    }
    for name, fn in ops.items():
        ms = timed_call_s(jax.jit(fn), x, warmup=2, repeat=repeat) * 1e3
        rows[name] = {"ms": round(ms, 3)}
    return rows


def cmd_bench(args: argparse.Namespace) -> int:
    """``byzpy-tpu bench``: print the on-device micro-benchmark as JSON."""
    report = bench_report(n=args.nodes, d=args.dim, repeat=args.repeat)
    print(json.dumps(report, indent=2, sort_keys=True))
    return 0


def cmd_lint(args: argparse.Namespace) -> int:
    """``byzpy-tpu lint``: run the byzlint static-analysis gate (exactly
    equivalent to ``python -m byzpy_tpu.analysis``; see
    ``docs/static_analysis.md`` for the rule catalog)."""
    from .analysis import main as lint_main

    argv: List[str] = list(args.paths)
    if args.format != "text":
        argv += ["--format", args.format]
    if args.select:
        argv += ["--select", args.select]
    if args.list_rules:
        argv += ["--list-rules"]
    return lint_main(argv)


def cmd_study(args: argparse.Namespace) -> int:
    """``byzpy-tpu study``: one accuracy-under-attack cell pair on real
    data — the 30-second proof that robust aggregation rescues training a
    byzantine attack destroys (full grid: ``benchmarks/robust_learning.py``)."""
    from .utils.robust_study import StudyConfig, results_table, run_study

    cfg = StudyConfig(rounds=args.rounds, eval_every=max(1, args.rounds // 3))
    aggregators = tuple(dict.fromkeys(("mean", args.aggregator)))
    results = run_study(
        aggregators=aggregators,
        attacks=(args.attack,),
        cfg=cfg,
        verbose=True,
    )
    print()
    print(results_table(results))
    return 0


def build_parser() -> argparse.ArgumentParser:
    """Assemble the ``byzpy-tpu`` argument parser (one subcommand per cmd_*)."""
    parser = argparse.ArgumentParser(
        prog="byzpy-tpu",
        description="TPU-native Byzantine-robust distributed learning framework",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p_version = sub.add_parser("version", help="print the package version")
    p_version.set_defaults(fn=cmd_version)

    p_doctor = sub.add_parser("doctor", help="report the JAX/TPU environment")
    p_doctor.add_argument("--format", choices=("text", "json"), default="text")
    p_doctor.set_defaults(fn=cmd_doctor)

    p_list = sub.add_parser("list", help="list available operator classes")
    p_list.add_argument(
        "kind", choices=("aggregators", "attacks", "pre-aggregators")
    )
    p_list.set_defaults(fn=cmd_list)

    p_bench = sub.add_parser(
        "bench", help="quick on-device micro-benchmark of the hot aggregators"
    )
    p_bench.add_argument("--nodes", type=int, default=16)
    p_bench.add_argument("--dim", type=int, default=65_536)
    p_bench.add_argument("--repeat", type=int, default=10)
    p_bench.set_defaults(fn=cmd_bench)

    p_lint = sub.add_parser(
        "lint",
        help="run byzlint, the JAX-aware static-analysis gate "
        "(trace-safety, donation, collective-axis, async hazards)",
    )
    p_lint.add_argument(
        "paths",
        nargs="*",
        help="files/dirs to scan (default: byzpy_tpu benchmarks examples)",
    )
    p_lint.add_argument("--format", choices=("text", "json"), default="text")
    p_lint.add_argument(
        "--select", default=None, metavar="RULES",
        help="comma-separated rule ids to run",
    )
    p_lint.add_argument("--list-rules", action="store_true")
    p_lint.set_defaults(fn=cmd_lint)

    p_study = sub.add_parser(
        "study",
        help="robust-learning demo: mean vs a robust aggregator under attack",
    )
    # mirrors utils.robust_study.STUDY_AGGREGATORS/STUDY_ATTACKS (kept
    # literal so `byzpy-tpu version` never imports jax; sync pinned by
    # tests/test_cli_utils_configs.py)
    p_study.add_argument(
        "--aggregator",
        default="trimmed_mean",
        choices=(
            "mean", "median", "trimmed_mean", "multi_krum",
            "geometric_median", "nnm_trimmed_mean",
        ),
    )
    p_study.add_argument(
        "--attack",
        default="sign_flip",
        choices=("none", "sign_flip", "empire", "little", "gaussian", "mimic"),
    )
    p_study.add_argument("--rounds", type=int, default=120)
    p_study.set_defaults(fn=cmd_study)

    return parser


def main(argv: List[str] | None = None) -> int:
    """Console entry point (``byzpy-tpu`` in pyproject scripts)."""
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
