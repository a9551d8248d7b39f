"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``, on the machine it is started
on: a TPU with the chips the cell asks for, or a non-zero exit before
anything compiles. The last line of standard output is the result object;
earlier lines are information (every one names the device).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tpu_devices(chips: int) -> list:
    """The first ``chips`` devices when JAX's backend is a TPU with at
    least that many; else exit non-zero, naming what was found. Compiles
    nothing. No flag or variable turns this into a CPU run."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:  # JAX found no backend it may use
        raise SystemExit(f"chipbench: no accelerator: {exc}")
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        raise SystemExit(
            f"chipbench: needs a TPU, JAX reports platform(s) {platforms} "
            f"({len(devices)} device(s), JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chipbench: the cell needs {chips} chip(s), JAX reports {len(devices)}"
        )
    return list(devices[:chips])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                        help="another manifest than BENCHMARK.json (selftests, trials)")
    parser.add_argument("--control", default=None,
                        help="run one of the configuration's lower-precision controls "
                             "(never used by a benchmark run)")
    args = parser.parse_args(argv)

    from chipbench import harness

    manifest = harness.load_json(args.manifest)
    cell = harness.find_cell(manifest, args.workload)
    devices = tpu_devices(int(cell["chips"]))

    from byzpy_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f'{{"compile_cache": "{cache_dir}"}}', flush=True)
    line = harness.run_cell(
        manifest, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, t_process=T_PROCESS, control=args.control,
    )
    return 0 if line is not None else 1


if __name__ == "__main__":
    sys.exit(main())
