"""Share of one step's `round.fwdbwd` device time that no part owns: ops whose
`op_name` path holds no `model.*` label (`model.mtp`, an envelope, left out)
and no `stream.*` label, over the sum of all parts (`chipbench/scope_parts.py`;
the run's `leading_ops` line names them under `unlabelled`). `None` where the
compiled step holds no `model.*` label at all. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    found = scope_parts.parts(ctx)
    total = sum(found.values()) if found else 0.0
    return 100.0 * found.get(scope_parts.UNLABELLED, 0.0) / total if total else None
