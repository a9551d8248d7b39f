"""Chaos grid: the standing (attack × fault × aggregator × precision)
regression wall.

Four lanes, each emitting JSON rows (stdout + ``--out`` JSONL):

* ``grid`` — every (attack × fault × aggregator) cell runs one
  declarative :class:`~byzpy_tpu.chaos.Scenario` through the chaos
  harness (direct masked-aggregate engine), paired with its attack-free
  twin for the contained/breached verdict. Each row carries the cell's
  event-trace digest — the replay pin: a future PR that changes any
  cell's behavior changes its digest, and `--smoke` asserts zero
  harness-crashed cells. A second pass replays the fault="none" plane
  at ``precision=int8`` (the PR-3 wire codec) — the grid's precision
  axis.
* ``adaptive`` — the head-to-head: each adaptive attacker vs its static
  counterpart on the aggregators it targets, reporting the influence
  uplift and exclusion-round gap (the ROADMAP's "adaptive attackers
  that optimize their next submission" made measurable).
* ``serving`` — staleness-window abuse against the REAL serving
  frontend admission path (virtual clock): the attacker stamps at the
  cutoff and pre-inflates by 1/discount so the tier's staleness
  discount cancels; outcome per aggregator reported as contained or
  breached vs the attack-free baseline (threat model: docs/serving.md).
* ``swarm`` — thousands of simulated clients (default 3,000) through
  the production admission gates under bursty arrivals, crashes and a
  partition, with adaptive byzantine clients riding along: sustained
  submissions/sec, rounds closed, zero failed rounds, full rejection
  accounting.
* ``recovery`` — REAL faults, not scenario events: per seed, a durable
  TCP frontend subprocess is SIGKILLed mid-round and recovered
  (``byzpy_tpu.resilience.drill``), asserting no accepted-then-lost
  submissions, exactly-once folding of replayed ``(client, seq)``
  frames, monotonic round numbering and digest continuity; plus an
  in-process ack-drop/retry cycle asserting round-aggregate bit parity
  against the no-fault twin. The standing wall runs ≥ 20 seeds.
* ``forensics`` — detector scoring for the PR-10 attribution plane
  (``byzpy_tpu.forensics``): every PR-7 adaptive attacker
  (influence-ascent, Krum-evasion, staleness-abuse) plus the static
  sign-flip/outlier attacks, run with the forensics plane attached —
  per-cell byzantine recall, first-flag round (must beat
  ``DETECT_BUDGET``), precision, and honest-contamination rate; an
  honest-only sweep pinning the false-positive rate under
  ``FP_BOUND``; trace-digest parity forensics-on vs forensics-off
  (the plane is a pure observer); and an end-to-end audit leg — a
  REAL durable serving frontend under staleness abuse, evidence
  verified present in the WAL (``python -m byzpy_tpu.forensics``
  report path) and on a live Prometheus scrape of the TCP ingress.
  The headline criterion: the staleness-abuse breach that was
  operator-invisible in PR 7 (trimmed-mean 8.4×, Multi-Krum 47×) now
  raises ``staleness_inflation`` flags within ``DETECT_BUDGET``
  rounds at a pinned honest false-positive rate.

* ``subint8`` — the adversarial-residual lane (round 15): the
  residual-shaping attacker (an encoder-controlling client inflating
  its per-block scales by κ and steering the coarse grid's rounding
  error through error feedback) through the REAL serving admission
  path per aggregator × sub-int8 fabric ({fp8, s4}), measured for
  influence vs its unshaped influence-ascent twin and screened by the
  forensics ``residual_shaping`` detector (pre-decode per-block
  inflation ratio — honest encoders sit at exactly 1.0) with the
  honest false-positive rate pinned under ``FP_BOUND``; plus the
  per-aggregator × attack precision-floor table (Byzantine tolerance
  over wire-quantization error, int8 → fp8 → fp8_e5m2 → s4).

* ``sanitize`` — the runtime invariant sanitizer
  (``byzpy_tpu.analysis.sanitize``, ISSUE 20) as a pure observer: one
  serving-engine cell runs hooks-off then hooks-on; the sanitized run
  must record zero violations, exercise the exactly-once fold audit
  (nonzero counters), and keep the event-trace digest bit-identical
  to the unsanitized twin.

``--smoke`` shrinks everything for CI and asserts the contracts (zero
harness-crashed cells, cell replay determinism, swarm liveness, zero
recovery-invariant violations). ``--lanes`` selects a subset (e.g.
``--lanes recovery``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU mesh: the chaos fabric is host-side machinery measured on the CPU
# mesh by design (same policy as serving_bench).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from byzpy_tpu.chaos import (  # noqa: E402
    ArrivalModel,
    AttackSpec,
    ChaosHarness,
    CrashModel,
    FaultPlan,
    PartitionEvent,
    Scenario,
    StragglerModel,
)


def _emit(row: dict, out_path: str | None) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    if out_path:
        with open(out_path, "a") as fh:
            fh.write(line + "\n")


# ---------------------------------------------------------------------------
# grid lane
# ---------------------------------------------------------------------------

ATTACK_CELLS = [
    # reference sign convention (attacks/sign_flip.py, attacks/empire.py):
    # negative scale = inverted direction
    ("sign_flip", {"scale": -4.0}),
    ("empire", {"scale": -1.1}),
    ("little", {"scale": 1.0}),
    ("outlier", {"scale": 50.0}),
    ("influence_ascent", {"grow": 1.8, "scale0": 0.1}),
    ("krum_evasion", {}),
]

FAULT_CELLS = {
    "none": FaultPlan(),
    "stragglers": FaultPlan(
        stragglers=StragglerModel(
            kind="bimodal", mu=-4.0, sigma=0.5, tail_prob=0.25, tail_s=0.5
        )
    ),
    "crash_restart": FaultPlan(
        crash=CrashModel(prob_per_round=0.03, restart_after_rounds=4)
    ),
    "partition": FaultPlan(
        partitions=(PartitionEvent(start_round=6, end_round=14, fraction=0.25),)
    ),
}

AGG_CELLS = [
    ("trimmed_mean", {"f": 3}),
    ("multi_krum", {"f": 3, "q": 4}),
    ("cge", {"f": 3}),
]

#: breached = the attack dragged the final params more than this factor
#: past the attack-free twin's error (plus an absolute floor so a
#: near-zero baseline can't declare breaches on noise)
BREACH_RATIO = 3.0
BREACH_FLOOR = 0.15


def _base_scenario(args, fault_name: str, **kwargs) -> Scenario:
    return Scenario(
        seed=args.seed,
        n_clients=args.clients_grid,
        dim=args.dim,
        rounds=args.rounds,
        faults=FAULT_CELLS[fault_name],
        **kwargs,
    )


def _verdict(err: float, baseline: float) -> str:
    return (
        "breached"
        if err > max(BREACH_RATIO * baseline, baseline + BREACH_FLOOR)
        else "contained"
    )


def _run_cell(scenario: Scenario, baseline_err: float) -> dict:
    """One grid cell, crash-guarded: the wall must report a broken cell,
    not die on it."""
    try:
        report = ChaosHarness(scenario).run()
        row = report.summary()
        row["baseline_error"] = round(baseline_err, 6)
        row["error_ratio"] = round(
            report.final_error / max(baseline_err, 1e-9), 3
        )
        row["verdict"] = _verdict(report.final_error, baseline_err)
        row["harness_crashed"] = False
    except Exception as exc:  # noqa: BLE001 — the wall reports, not dies
        row = {
            "scenario": scenario.name,
            "attack": scenario.attack.name,
            "aggregator": scenario.aggregator,
            "precision": scenario.precision,
            "harness_crashed": True,
            "error": f"{type(exc).__name__}: {exc}",
        }
    return row


def run_grid(args, out) -> list:
    rows = []
    for fault_name in args.faults:
        for agg_name, agg_params in args.aggregators:
            base = _base_scenario(
                args,
                fault_name,
                name=f"baseline/{fault_name}/{agg_name}",
                aggregator=agg_name,
                aggregator_params=agg_params,
            )
            baseline = ChaosHarness(base).run()
            for attack_name, attack_params in args.attacks:
                cell = base.with_(
                    name=f"grid/{attack_name}/{fault_name}/{agg_name}",
                    n_byzantine=args.byzantine,
                    attack=AttackSpec(name=attack_name, params=attack_params),
                )
                row = {"lane": "grid", "fault": fault_name}
                row.update(_run_cell(cell, baseline.final_error))
                rows.append(row)
                _emit(row, out)
    # precision axis: the fault-free plane again through the int8 wire
    # codec — robust verdicts must hold on compressed submissions
    for agg_name, agg_params in args.aggregators:
        base = _base_scenario(
            args,
            "none",
            name=f"baseline/int8/{agg_name}",
            aggregator=agg_name,
            aggregator_params=agg_params,
            precision="int8",
        )
        baseline = ChaosHarness(base).run()
        for attack_name, attack_params in args.attacks:
            cell = base.with_(
                name=f"grid/{attack_name}/none+int8/{agg_name}",
                n_byzantine=args.byzantine,
                attack=AttackSpec(name=attack_name, params=attack_params),
            )
            row = {"lane": "grid", "fault": "none"}
            row.update(_run_cell(cell, baseline.final_error))
            rows.append(row)
            _emit(row, out)
    return rows


# ---------------------------------------------------------------------------
# adaptive head-to-head lane
# ---------------------------------------------------------------------------

#: (adaptive, static counterpart, aggregator) triples: the same attack
#: budget, blind vs observing
PAIRS = [
    ("influence_ascent", {"grow": 1.8, "scale0": 0.1},
     "outlier", {"scale": 50.0}, "multi_krum", {"f": 3, "q": 4}),
    ("influence_ascent", {"grow": 1.8, "scale0": 0.1},
     "outlier", {"scale": 50.0}, "cge", {"f": 3}),
    ("krum_evasion", {}, "outlier", {"scale": 50.0},
     "multi_krum", {"f": 3, "q": 4}),
]


def run_adaptive(args, out) -> list:
    rows = []
    for a_name, a_params, s_name, s_params, agg, agg_params in PAIRS:
        reports = {}
        for name, params in ((a_name, a_params), (s_name, s_params)):
            cell = _base_scenario(
                args,
                "none",
                name=f"adaptive/{name}/{agg}",
                aggregator=agg,
                aggregator_params=agg_params,
                n_byzantine=args.byzantine,
                attack=AttackSpec(name=name, params=params),
            )
            reports[name] = ChaosHarness(cell).run()
        adaptive, static = reports[a_name], reports[s_name]
        row = {
            "lane": "adaptive",
            "aggregator": agg,
            "adaptive": a_name,
            "static": s_name,
            "adaptive_influence_mean": round(adaptive.influence_mean, 6),
            "static_influence_mean": round(static.influence_mean, 6),
            "influence_uplift": round(
                adaptive.influence_mean / max(static.influence_mean, 1e-9), 2
            ),
            "adaptive_last_selected_round": adaptive.last_selected_round,
            "static_last_selected_round": static.last_selected_round,
            "adaptive_final_error": round(adaptive.final_error, 6),
            "static_final_error": round(static.final_error, 6),
            "adaptive_beats_static": bool(
                adaptive.influence_mean > static.influence_mean
                or adaptive.last_selected_round > static.last_selected_round
            ),
        }
        rows.append(row)
        _emit(row, out)
    return rows


# ---------------------------------------------------------------------------
# serving staleness-abuse lane
# ---------------------------------------------------------------------------


def run_serving(args, out) -> list:
    rows = []
    cutoff, gamma = 4, 0.5
    for agg_name, agg_params in args.aggregators:
        common = dict(
            seed=args.seed,
            n_clients=args.clients_grid,
            dim=args.dim,
            rounds=args.rounds,
            engine="serving",
            aggregator=agg_name,
            aggregator_params=agg_params,
            staleness_kind="exponential",
            staleness_gamma=gamma,
            staleness_cutoff=cutoff,
        )
        baseline = ChaosHarness(
            Scenario(name=f"serving-baseline/{agg_name}", **common)
        ).run()
        abuse = ChaosHarness(
            Scenario(
                name=f"serving-abuse/{agg_name}",
                n_byzantine=args.byzantine,
                attack=AttackSpec(
                    name="staleness_abuse",
                    params={"kind": "exponential", "gamma": gamma,
                            "cutoff": cutoff, "scale": 2.0},
                ),
                **common,
            )
        ).run()
        row = {
            "lane": "serving",
            "aggregator": agg_name,
            "attack": "staleness_abuse",
            "staleness": {"kind": "exponential", "gamma": gamma,
                          "cutoff": cutoff},
            "inflation": round((1.0 / gamma) ** cutoff, 1),
            "rounds": abuse.rounds_completed,
            "verdicts": dict(abuse.verdict_counts),
            "influence_mean": round(abuse.influence_mean, 6),
            "baseline_error": round(baseline.final_error, 6),
            "final_error": round(abuse.final_error, 6),
            "error_ratio": round(
                abuse.final_error / max(baseline.final_error, 1e-9), 3
            ),
            "outcome": _verdict(abuse.final_error, baseline.final_error),
            "trace_digest": abuse.trace.digest(),
        }
        rows.append(row)
        _emit(row, out)
    return rows


# ---------------------------------------------------------------------------
# recovery lane (real faults: SIGKILL + wire drops)
# ---------------------------------------------------------------------------


def run_recovery(args, out) -> dict:
    import tempfile

    from byzpy_tpu.resilience import drill as rdrill

    kill_rows, wire_rows = [], []
    for i in range(args.recovery_runs):
        seed = args.seed + i
        with tempfile.TemporaryDirectory() as tmp:
            row = rdrill.run_kill_recover(seed, tmp)
        kill_rows.append(row)
        _emit(row, out)
        wrow = rdrill.run_wire_drop(seed)
        wire_rows.append(wrow)
        _emit(wrow, out)
    summary = {
        "lane": "recovery_summary",
        "runs": args.recovery_runs,
        "kill_violations": sum(r["violations"] for r in kill_rows),
        "wire_violations": sum(r["violations"] for r in wire_rows),
        "acked_accepted_total": sum(r["acked_accepted"] for r in kill_rows),
        "lost_total": sum(r["lost"] for r in kill_rows),
        "double_folded_total": sum(r["double_folded"] for r in kill_rows),
        "duplicates_absorbed_total": sum(
            r["duplicates_absorbed"] for r in kill_rows + wire_rows
        ),
        "bit_parity_runs": sum(1 for r in wire_rows if r["bit_parity"]),
        "mean_kill_recover_wall_s": round(
            float(np.mean([r["wall_s"] for r in kill_rows])), 3
        ),
        "recovery_metric_exported": all(
            r["recovery_metric_exported"] for r in kill_rows
        ),
        "checkpoint_metric_exported": all(
            r["checkpoint_metric_exported"] for r in kill_rows
        ),
        # the registry counter is process-cumulative: the last run's
        # reading IS the lane total (summing would double-count)
        "retry_total": wire_rows[-1]["retry_total"] if wire_rows else 0.0,
    }
    _emit(summary, out)
    return summary


# ---------------------------------------------------------------------------
# forensics lane (detector scoring for the attribution plane)
# ---------------------------------------------------------------------------

#: Detection budget: every adaptive attacker must raise its first flag
#: within this many rounds (the PR-7 serving-lane breach was invisible
#: for the WHOLE run).
DETECT_BUDGET = 6
#: Pinned honest-only false-positive bound (fraction of honest
#: client-round records carrying any flag; measured worst across the
#: committed sweep: 0.014).
FP_BOUND = 0.02

_SERVING_STALENESS = dict(
    engine="serving",
    staleness_kind="exponential",
    staleness_gamma=0.5,
    staleness_cutoff=4,
)

#: (attack, params, aggregator, agg_params, scenario extras, adaptive?)
FORENSICS_CELLS = [
    ("influence_ascent", {"grow": 1.8, "scale0": 0.1},
     "multi_krum", {"f": 3, "q": 4}, {}, True),
    ("influence_ascent", {"grow": 1.8, "scale0": 0.1},
     "cge", {"f": 3}, {}, True),
    ("krum_evasion", {}, "multi_krum", {"f": 3, "q": 4}, {}, True),
    ("staleness_abuse",
     {"kind": "exponential", "gamma": 0.5, "cutoff": 4, "scale": 2.0},
     "trimmed_mean", {"f": 3}, _SERVING_STALENESS, True),
    ("staleness_abuse",
     {"kind": "exponential", "gamma": 0.5, "cutoff": 4, "scale": 2.0},
     "multi_krum", {"f": 3, "q": 4}, _SERVING_STALENESS, True),
    ("sign_flip", {"scale": -4.0}, "trimmed_mean", {"f": 3}, {}, False),
    ("outlier", {"scale": 50.0}, "multi_krum", {"f": 3, "q": 4}, {}, False),
]

HONEST_CONFIGS = [
    ("trimmed_mean", {"f": 3}, {}),
    ("multi_krum", {"f": 3, "q": 4}, {}),
    ("cge", {"f": 3}, {}),
    ("trimmed_mean", {"f": 3}, _SERVING_STALENESS),
]


def _forensics_config():
    from byzpy_tpu.forensics import ForensicsConfig

    return ForensicsConfig()


def run_forensics(args, out) -> dict:
    rows = []
    fc = _forensics_config()
    # -- attack cells: recall / first-flag / precision ------------------
    for att, ap, agg, agp, extra, adaptive in args.forensics_cells:
        cell = Scenario(
            name=f"forensics/{att}/{agg}",
            seed=args.seed,
            n_clients=args.clients_grid,
            n_byzantine=args.byzantine,
            dim=args.dim,
            rounds=args.rounds,
            aggregator=agg,
            aggregator_params=agp,
            attack=AttackSpec(name=att, params=ap),
            **extra,
        )
        report = ChaosHarness(cell, forensics=fc).run()
        s = report.forensics_summary()
        row = {
            "lane": "forensics",
            "attack": att,
            "adaptive": adaptive,
            "aggregator": agg,
            "engine": cell.engine,
            "rounds": report.rounds_completed,
            "byz_present": s["byz_present"],
            "byz_flagged": s["byz_flagged"],
            "recall": s["recall"],
            "precision": s["precision"],
            "first_byz_flag_round": s["first_byz_flag_round"],
            "honest_fp_rate": round(s["honest_fp_rate"], 4),
            "flags_by_detector": s["flags_by_detector"],
            "detect_budget": DETECT_BUDGET,
            "within_budget": (
                s["first_byz_flag_round"] is not None
                and s["first_byz_flag_round"] <= DETECT_BUDGET
            ),
            "final_error": round(report.final_error, 6),
            "trace_digest": report.trace.digest(),
        }
        rows.append(row)
        _emit(row, out)
    # -- honest-only sweep: pinned false-positive bound -----------------
    worst_fp = 0.0
    honest_runs = 0
    for i in range(args.forensics_honest_seeds):
        for agg, agp, extra in args.honest_configs:
            cell = Scenario(
                name=f"forensics-honest/{agg}",
                seed=args.seed + i,
                n_clients=args.clients_grid,
                dim=args.dim,
                rounds=args.rounds,
                aggregator=agg,
                aggregator_params=agp,
                **extra,
            )
            s = ChaosHarness(cell, forensics=fc).run().forensics_summary()
            worst_fp = max(worst_fp, s["honest_fp_rate"])
            honest_runs += 1
    # -- digest parity: the plane is a pure observer --------------------
    parity_cell = Scenario(
        name="forensics-parity",
        seed=args.seed,
        n_clients=args.clients_grid,
        n_byzantine=args.byzantine,
        dim=args.dim,
        rounds=args.rounds,
        aggregator="multi_krum",
        aggregator_params={"f": 3, "q": 4},
        attack=AttackSpec(
            name="influence_ascent", params={"grow": 1.8, "scale0": 0.1}
        ),
    )
    with_f = ChaosHarness(parity_cell, forensics=fc).run()
    without = ChaosHarness(parity_cell).run()
    digest_parity = (
        with_f.trace.digest() == without.trace.digest()
        and with_f.final_error == without.final_error
    )
    # -- end-to-end audit: durable frontend + WAL + Prometheus ----------
    audit_row = _forensics_audit_leg(args)
    _emit(audit_row, out)
    summary = {
        "lane": "forensics_summary",
        "cells": len(rows),
        "adaptive_cells": sum(1 for r in rows if r["adaptive"]),
        "adaptive_all_flagged": all(
            r["byz_flagged"] == r["byz_present"]
            for r in rows
            if r["adaptive"]
        ),
        "adaptive_within_budget": all(
            r["within_budget"] for r in rows if r["adaptive"]
        ),
        "staleness_first_flag": {
            r["aggregator"]: r["first_byz_flag_round"]
            for r in rows
            if r["attack"] == "staleness_abuse"
        },
        "honest_runs": honest_runs,
        "honest_worst_fp_rate": round(worst_fp, 4),
        "fp_bound": FP_BOUND,
        "fp_within_bound": worst_fp <= FP_BOUND,
        "digest_parity": digest_parity,
        "wal_audit_ok": audit_row["wal_audit_ok"],
        "prometheus_ok": audit_row["prometheus_ok"],
    }
    _emit(summary, out)
    return summary


def _forensics_audit_leg(args) -> dict:
    """A REAL durable ServingFrontend under staleness abuse: evidence
    must land in the write-ahead log (readable by the forensics CLI's
    audit path) and the forensics metric families must answer on a
    live Prometheus scrape of the TCP wire ingress."""
    import asyncio
    import tempfile

    import numpy as np

    from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
    from byzpy_tpu.forensics import ForensicsConfig, TrustPolicy, audit
    from byzpy_tpu.serving import (
        DurabilityConfig,
        ServingFrontend,
        StalenessPolicy,
        TenantConfig,
    )

    rounds = max(6, min(10, args.rounds))
    dim = 16

    async def drive(tmp: str) -> dict:
        fe = ServingFrontend(
            [
                TenantConfig(
                    name="m0",
                    aggregator=CoordinateWiseTrimmedMean(f=1),
                    dim=dim,
                    staleness=StalenessPolicy(
                        kind="exponential", gamma=0.5, cutoff=4
                    ),
                    forensics=ForensicsConfig(
                        trust=TrustPolicy(alpha=0.5, readmit_after_rounds=4),
                        quarantine=True,
                    ),
                )
            ],
            # prune=False keeps the full forensic history on disk —
            # the audit must see every round's evidence
            durability=DurabilityConfig(directory=tmp, prune=False),
        )
        rng = np.random.default_rng(args.seed)
        untrusted_acks = 0
        for r in range(rounds):
            for i in range(6):
                ok, reason = fe.submit(
                    "m0", f"c{i}", r,
                    rng.normal(1.0, 0.1, dim).astype(np.float32),
                )
                assert ok, reason
            # the staleness abuser: stamps at the cutoff, pre-inflates
            # by 1/discount(4) = 16x so the discount cancels at fold
            inflated = (16.0 * rng.normal(1.0, 0.1, dim)).astype(np.float32)
            ok, reason = fe.submit("m0", "byz0", max(0, r - 4), inflated)
            if reason == "rejected_untrusted":
                untrusted_acks += 1
            assert fe.close_round_nowait("m0") is not None
        host, port = await fe.serve()
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
        await writer.drain()
        scrape = (await reader.read(-1)).decode()
        writer.close()
        stats = fe.stats()["m0"]
        await fe.close()
        return {"scrape": scrape, "stats": stats, "untrusted": untrusted_acks}

    with tempfile.TemporaryDirectory() as tmp:
        res = asyncio.run(drive(tmp))
        report = audit.wal_timeline(os.path.join(tmp, "m0"))
    byz_entry = report["clients"].get("byz0", {})
    wal_ok = (
        report["evidence_rounds"] > 0
        and not report["digest_mismatches"]
        and bool(byz_entry.get("flags"))
        and any(t["event"] == "quarantine" for t in report["transitions"])
    )
    prom_ok = all(
        name in res["scrape"]
        for name in (
            "byzpy_anomaly_flags_total",
            "byzpy_trust_score",
            "byzpy_client_excluded_total",
            "byzpy_quarantined_clients",
        )
    )
    return {
        "lane": "forensics_audit",
        "rounds": rounds,
        "wal_evidence_rounds": report["evidence_rounds"],
        "wal_digest_mismatches": len(report["digest_mismatches"]),
        "byz_flags": dict(byz_entry.get("flags", {})),
        "quarantine_transitions": len(report["transitions"]),
        "rejected_untrusted_acks": res["untrusted"],
        "wal_audit_ok": wal_ok,
        "prometheus_ok": prom_ok,
    }


# ---------------------------------------------------------------------------
# swarm lane
# ---------------------------------------------------------------------------


def run_ragged(args, out) -> dict:
    """Ragged-door parity cell (PR 11): one serving-engine cell replayed
    through the DEFAULT ragged dispatcher and again through the
    bucket-ladder escape hatch (``BYZPY_TPU_RAGGED=0``) — the event
    traces fold every round's exact aggregate bits into their digests,
    so digest equality IS the bit-parity pin keeping the regression
    wall honest about which door served it. Asserted unconditionally
    (the cell is cheap; a parity break must never ride a green wall)."""
    agg_name, agg_params = args.aggregators[0]
    scenario = Scenario(
        name=f"ragged-door/{agg_name}",
        seed=args.seed,
        n_clients=args.clients_grid,
        n_byzantine=args.byzantine,
        dim=args.dim,
        rounds=args.rounds,
        engine="serving",
        aggregator=agg_name,
        aggregator_params=agg_params,
        staleness_kind="exponential",
        staleness_gamma=0.5,
        staleness_cutoff=4,
        attack=AttackSpec(
            name="staleness_abuse",
            params={"kind": "exponential", "gamma": 0.5,
                    "cutoff": 4, "scale": 2.0},
        ),
    )
    prev = os.environ.get("BYZPY_TPU_RAGGED")
    try:
        os.environ.pop("BYZPY_TPU_RAGGED", None)
        ragged = ChaosHarness(scenario).run()
        os.environ["BYZPY_TPU_RAGGED"] = "0"
        bucketed = ChaosHarness(scenario).run()
    finally:
        if prev is None:
            os.environ.pop("BYZPY_TPU_RAGGED", None)
        else:
            os.environ["BYZPY_TPU_RAGGED"] = prev
    row = {
        "lane": "ragged",
        "aggregator": agg_name,
        "rounds": ragged.rounds_completed,
        "ragged_digest": ragged.trace.digest(),
        "bucketed_digest": bucketed.trace.digest(),
        "digest_match": ragged.trace.digest() == bucketed.trace.digest(),
    }
    _emit(row, out)
    assert row["digest_match"], (
        "ragged door diverged from the bucket ladder: "
        f"{row['ragged_digest']} != {row['bucketed_digest']}"
    )
    return row


def run_shard(args, out) -> dict:
    """Sharded-tier cell (ISSUE 12): (1) hierarchical-fold BIT PARITY —
    the same deterministic client population served by a 2-shard
    :class:`~byzpy_tpu.serving.ShardedCoordinator` and by ONE
    :class:`~byzpy_tpu.serving.ServingFrontend` fed the concatenated
    (shard-order) cohorts must produce digest-identical aggregates
    every round; (2) the compromised-shard adversary — a Byzantine
    shard forging its PartialFold (rows tampered after the digest, a
    ghost-client claim, poisoned extras) must be flagged by the root's
    evidence-digest cross-check every round it forges, with the merged
    aggregate bit-identical to the honest-shards-only reference.
    Asserted unconditionally (a parity or detection break must never
    ride a green wall)."""
    from byzpy_tpu.aggregators import MultiKrum
    from byzpy_tpu.chaos.shards import CompromisedShard
    from byzpy_tpu.forensics.evidence import evidence_digest
    from byzpy_tpu.serving import (
        ServingFrontend,
        ShardedCoordinator,
        TenantConfig,
    )
    from byzpy_tpu.serving.sharded import shard_for
    from byzpy_tpu.serving.staleness import StalenessPolicy

    dim = args.dim
    rounds = max(4, args.rounds // 4)
    n_clients = max(8, args.clients_grid)
    rng = np.random.default_rng(args.seed)
    clients = [f"c{i:04d}" for i in range(n_clients)]
    grads = {c: rng.normal(size=dim).astype(np.float32) for c in clients}

    def mk_tenants():
        return [
            TenantConfig(
                name="m0",
                aggregator=MultiKrum(f=args.byzantine, q=args.byzantine + 1),
                dim=dim,
                cohort_cap=max(n_clients, 8),
                staleness=StalenessPolicy(
                    kind="exponential", gamma=0.5, cutoff=8
                ),
            )
        ]

    # -- parity cell: 2 shards vs one frontend, digest equality ----------
    n_shards = 2
    co = ShardedCoordinator(mk_tenants(), n_shards, quorum=1)
    co_s = ShardedCoordinator(mk_tenants(), n_shards, quorum=1)
    co_c = ShardedCoordinator(mk_tenants(), n_shards, quorum=1)
    fe = ServingFrontend(mk_tenants())
    order = [
        c
        for s in range(n_shards)
        for c in clients
        if shard_for(c, n_shards) == s
    ]
    parity_digests = []
    for r in range(rounds):
        for c in clients:
            ok, reason = co.submit("m0", c, r, grads[c], seq=r)
            assert ok, (c, reason)
            ok, reason = co_s.submit("m0", c, r, grads[c], seq=r)
            assert ok, (c, reason)
            ok, reason = co_c.submit("m0", c, r, grads[c], seq=r)
            assert ok, (c, reason)
        res = co.close_round_nowait("m0")
        assert res is not None
        # streaming twin: each partial cross-checked AT ARRIVAL
        # (reverse arrival order — arrival order must not matter),
        # then merged with the cached verdicts (ISSUE 18)
        stream_parts = [
            co_s.shards[s].close_partial("m0") for s in range(n_shards)
        ]
        assert all(p is not None for p in stream_parts)
        prechecked = {
            id(p): co_s.check_partial("m0", p, inflight=True)
            for p in reversed(stream_parts)
        }
        res_s = co_s.merge_partials(
            "m0", stream_parts, prechecked=prechecked
        )
        assert res_s is not None, r
        # close-path twin (ISSUE 19): check + STAGE at arrival (dedup
        # verdict parked, cross-Gram blocks computed on the 'reader'
        # side), the close promotes — digest-identical, reverse order
        cp_parts = [
            co_c.shards[s].close_partial("m0") for s in range(n_shards)
        ]
        assert all(p is not None for p in cp_parts)
        cp_pre = {}
        for p in reversed(cp_parts):
            chk = co_c.check_partial("m0", p, inflight=True)
            cp_pre[id(p)] = chk
            assert chk[0] and co_c.stage_partial("m0", p, chk)
        res_c = co_c.merge_partials(
            "m0", cp_parts, prechecked=cp_pre
        )
        assert res_c is not None, r
        for c in order:
            ok, reason = fe.submit("m0", c, r, grads[c], seq=r)
            assert ok, (c, reason)
        ref = fe.close_round_nowait("m0")
        assert ref is not None
        sharded_digest = evidence_digest(res[2])
        single_digest = evidence_digest(ref[2])
        stream_digest = evidence_digest(res_s[2])
        parity_digests.append(
            {"round": r, "sharded": sharded_digest, "single": single_digest}
        )
        assert sharded_digest == single_digest, (
            f"hierarchical fold diverged at round {r}: "
            f"{sharded_digest} != {single_digest}"
        )
        assert stream_digest == sharded_digest, (
            f"streaming merge diverged at round {r}: "
            f"{stream_digest} != {sharded_digest}"
        )
        closepath_digest = evidence_digest(res_c[2])
        assert closepath_digest == sharded_digest, (
            f"close-path merge diverged at round {r}: "
            f"{closepath_digest} != {sharded_digest}"
        )
    assert co_s.stats()["root"]["m0"]["partial_checks"] == (
        rounds * n_shards
    )
    assert co_s.stats()["root"]["m0"]["partials_inflight"] == 0
    # close-path accounting at the combinatorial floor: every close
    # consumed the arrival-staged accumulator, the cross-Gram blocks
    # are exactly rounds·k·(k−1)/2, and no shard's shipped Gram was
    # ever recomputed (zero redundant extras recomputes, counter-pinned)
    cp_st = co_c.stats()["root"]["m0"]
    assert cp_st["staged_closes"] == rounds, cp_st
    assert cp_st["dedup_promoted"] == rounds * n_shards, cp_st
    assert cp_st["dedup_restaged"] == 0, cp_st
    assert cp_st["gram_cross_blocks"] == (
        rounds * n_shards * (n_shards - 1) // 2
    ), cp_st
    assert cp_st["partial_transforms"] == 0, cp_st
    assert cp_st["partials_inflight"] == 0, cp_st

    # -- compromised-shard cells: each forgery mode vs the root ----------
    forge_rows = {}
    for mode in ("bitflip", "ghost_clients", "extras"):
        n3 = 3
        co3 = ShardedCoordinator(
            mk_tenants(), n3, quorum=1, extras_policy="verify"
        )
        co3s = ShardedCoordinator(
            mk_tenants(), n3, quorum=1, extras_policy="verify"
        )
        byz = 2
        co3.shards[byz] = CompromisedShard(
            co3.shards[byz], mode=mode, seed=args.seed, n_shards=n3
        )
        co3s.shards[byz] = CompromisedShard(
            co3s.shards[byz], mode=mode, seed=args.seed, n_shards=n3
        )
        honest_clients = [c for c in clients if shard_for(c, n3) != byz]
        ref_co = ShardedCoordinator(mk_tenants(), n3, quorum=1)
        stream_forged = 0
        for r in range(rounds):
            for c in clients:
                ok, _ = co3.submit("m0", c, r, grads[c], seq=r)
                assert ok
                ok, _ = co3s.submit("m0", c, r, grads[c], seq=r)
                assert ok
            for c in honest_clients:
                ok, _ = ref_co.submit("m0", c, r, grads[c], seq=r)
                assert ok
            res = co3.close_round_nowait("m0")
            ref = ref_co.close_round_nowait("m0")
            assert res is not None and ref is not None
            # the forged partial was excluded: the merged aggregate is
            # bit-identical to the honest-shards-only deployment
            assert np.array_equal(res[2], ref[2]), (mode, r)
            # streaming twin: the forged frame fails its ARRIVAL-time
            # cross-check, and the cached verdict excludes it at the
            # close without poisoning the incremental merge state
            parts = [
                co3s.shards[s].close_partial("m0") for s in range(n3)
            ]
            assert all(p is not None for p in parts)
            prechecked = {
                id(p): co3s.check_partial("m0", p, inflight=True)
                for p in parts
            }
            forged_now = sum(
                1 for ok_chk, _m in prechecked.values() if not ok_chk
            )
            assert forged_now == 1, (mode, r, forged_now)
            stream_forged += forged_now
            res_s = co3s.merge_partials(
                "m0", parts, prechecked=prechecked
            )
            assert res_s is not None, (mode, r)
            assert np.array_equal(res_s[2], ref[2]), (mode, r)
        detected = co3.stats()["root"]["m0"]["forged_partials"]
        events = [
            e for e in co3.shard_events if e["event"] == "shard_forged"
        ]
        assert detected == rounds, (mode, detected, rounds)
        assert len(events) == rounds and all(
            e["shard"] == byz for e in events
        ), mode
        s_detected = co3s.stats()["root"]["m0"]["forged_partials"]
        assert s_detected == rounds, (mode, s_detected, rounds)
        assert co3s.stats()["root"]["m0"]["partials_inflight"] == 0
        forge_rows[mode] = {
            "rounds": rounds,
            "forged_detected": detected,
            "evidence_events": len(events),
            "aggregate_parity_vs_honest_only": "bit-identical",
            "streaming_forged_detected": stream_forged,
            "streaming_parity_vs_honest_only": "bit-identical",
        }

    row = {
        "lane": "shard",
        "aggregator": "multi-krum",
        "clients": n_clients,
        "shards_parity_cell": n_shards,
        "rounds": rounds,
        "parity": "bit-identical",
        "parity_digest_last": parity_digests[-1]["sharded"],
        "streaming_parity": "bit-identical",
        "streaming_checks": rounds * n_shards,
        "closepath_parity": "bit-identical",
        "closepath_staged_closes": cp_st["staged_closes"],
        "closepath_gram_cross_blocks": cp_st["gram_cross_blocks"],
        "closepath_partial_transforms": cp_st["partial_transforms"],
        "forgery": forge_rows,
    }
    _emit(row, out)
    return row


def run_speculative(args, out) -> dict:
    """Speculative quorum close + late-arrival repair (ISSUE 17): the
    always-on round door must be FORENSICALLY equivalent to the barrier
    it replaces.  Cells, asserted unconditionally:

    (1) repair BIT PARITY across seeds — a 3-shard coordinator with the
        repair horizon armed closes every round degraded (one straggler
        past the barrier), then folds the straggler's late partial
        through :meth:`ShardedCoordinator.repair_round`; the repaired
        aggregate must be bit-identical to a barrier twin that waited
        for all three shards, every round, every seed (late arrival
        must not change a single aggregate bit — same shard-order
        merge, same staleness discounts the rows were stamped with at
        their ORIGINAL round);
    (2) staleness abuse — replaying the already-repaired partial (the
        double-fold inflation an abuser would smuggle through the
        repair window) is rejected as a protocol violation without
        touching the aggregate;
    (3) forged late arrival — a compromised straggler's tampered
        partial is excluded by the same digest cross-check the barrier
        runs (the repair horizon is not a forensics bypass), with an
        evidence event and the degraded close left standing."""
    from byzpy_tpu.aggregators import MultiKrum
    from byzpy_tpu.chaos.shards import CompromisedShard
    from byzpy_tpu.forensics.evidence import evidence_digest
    from byzpy_tpu.serving import ShardedCoordinator, TenantConfig
    from byzpy_tpu.serving.staleness import StalenessPolicy

    dim = args.dim
    rounds = max(4, args.rounds // 4)
    n_clients = max(12, args.clients_grid)
    n_shards, straggler = 3, 2
    clients = [f"c{i:04d}" for i in range(n_clients)]

    def mk_tenants():
        return [
            TenantConfig(
                name="m0",
                aggregator=MultiKrum(f=args.byzantine, q=args.byzantine + 1),
                dim=dim,
                cohort_cap=max(n_clients, 8),
                staleness=StalenessPolicy(
                    kind="exponential", gamma=0.5, cutoff=8
                ),
            )
        ]

    seeds = [args.seed + k for k in range(3)]
    parity_rounds = 0
    for seed in seeds:
        rng = np.random.default_rng(seed)
        grads = {
            c: rng.normal(size=dim).astype(np.float32) for c in clients
        }
        co = ShardedCoordinator(
            mk_tenants(), n_shards, quorum=2, repair_horizon_rounds=2
        )
        twin = ShardedCoordinator(mk_tenants(), n_shards, quorum=1)
        for r in range(rounds):
            for c in clients:
                ok, reason = co.submit("m0", c, r, grads[c], seq=r)
                assert ok, (c, reason)
                ok, reason = twin.submit("m0", c, r, grads[c], seq=r)
                assert ok, (c, reason)
            ref = twin.close_round_nowait("m0")
            assert ref is not None
            # the straggler DRAINED at the barrier (its cohort is round
            # r's), but its reply is late: the root closes degraded at
            # quorum with the horizon armed...
            late = co.shards[straggler].close_partial("m0")
            assert late is not None
            present = [
                co.shards[s].close_partial("m0")
                for s in range(n_shards)
                if s != straggler
            ]
            res = co.merge_partials(
                "m0", [p for p in present if p is not None],
                missing=[straggler],
            )
            assert res is not None, (seed, r)
            # ...and the late arrival folds as a WAL-recorded repair
            # delta, bit-identical to the barrier twin's full close
            rep = co.repair_round("m0", late)
            assert rep is not None, (seed, r)
            assert rep[0] == r and ref[0] == r, (rep[0], ref[0])
            assert np.array_equal(rep[2], ref[2]), (
                f"repair diverged from barrier twin at seed {seed} "
                f"round {r}: {evidence_digest(rep[2])} != "
                f"{evidence_digest(ref[2])}"
            )
            parity_rounds += 1
            # staleness-abuse: replaying the repaired partial (double-
            # fold inflation) is a protocol violation — rejected, and
            # the aggregate does not move
            replay = co.repair_round("m0", late)
            assert replay is None, (seed, r)
        st = co.stats()["root"]["m0"]
        assert st["speculative_closes"] == rounds, st
        assert st["repairs"] == rounds, st
        assert st["open_repairs"] == 0, st

    # streaming repair (ISSUE 18): the late partial is cross-checked at
    # ARRIVAL and repair_round reuses the cached verdict — a repair
    # costs ZERO additional verifies at fold time, and the repaired
    # aggregate stays bit-identical to the barrier twin
    rng = np.random.default_rng(args.seed)
    grads = {c: rng.normal(size=dim).astype(np.float32) for c in clients}
    co_st = ShardedCoordinator(
        mk_tenants(), n_shards, quorum=2, repair_horizon_rounds=2
    )
    twin_st = ShardedCoordinator(mk_tenants(), n_shards, quorum=1)
    streaming_repair_rounds = 0
    for r in range(rounds):
        for c in clients:
            ok, _ = co_st.submit("m0", c, r, grads[c], seq=r)
            assert ok
            ok, _ = twin_st.submit("m0", c, r, grads[c], seq=r)
            assert ok
        ref = twin_st.close_round_nowait("m0")
        assert ref is not None
        late = co_st.shards[straggler].close_partial("m0")
        assert late is not None
        late_chk = co_st.check_partial("m0", late, inflight=True)
        present = [
            co_st.shards[s].close_partial("m0")
            for s in range(n_shards)
            if s != straggler
        ]
        prechecked = {
            id(p): co_st.check_partial("m0", p, inflight=True)
            for p in present
        }
        # close-path: the present partials stage at arrival (verdict +
        # fold + cross-Gram accumulation); the late straggler does NOT
        # stage — it repairs after the degraded close, exactly as before
        for p in present:
            chk = prechecked[id(p)]
            assert chk[0] and co_st.stage_partial("m0", p, chk), r
        res = co_st.merge_partials(
            "m0", present, missing=[straggler], prechecked=prechecked
        )
        assert res is not None, r
        checks_at_close = co_st.stats()["root"]["m0"]["partial_checks"]
        rep = co_st.repair_round("m0", late, prechecked=late_chk)
        assert rep is not None, r
        assert np.array_equal(rep[2], ref[2]), (
            f"streaming repair diverged at round {r}: "
            f"{evidence_digest(rep[2])} != {evidence_digest(ref[2])}"
        )
        # the repair consumed the arrival-time verdict: no new verify
        assert (
            co_st.stats()["root"]["m0"]["partial_checks"]
            == checks_at_close
        ), r
        streaming_repair_rounds += 1
    st_cp = co_st.stats()["root"]["m0"]
    assert st_cp["partials_inflight"] == 0
    # close-path pins: every degraded close consumed its staged
    # accumulator (verdicts promoted, zero restages), and the round's
    # Gram work is exactly the irreducible block set — one cross block
    # per staged close (2 present shards) plus the repair's re-merge
    # (C(3,2) blocks over present+late), with ZERO redundant diagonal
    # transforms (every partial shipped its Gram; nothing recomputed)
    assert st_cp["staged_closes"] == rounds, st_cp
    assert st_cp["dedup_promoted"] == rounds * (n_shards - 1), st_cp
    assert st_cp["dedup_restaged"] == 0, st_cp
    assert st_cp["partial_transforms"] == 0, st_cp
    assert st_cp["gram_cross_blocks"] == rounds * (
        1 + n_shards * (n_shards - 1) // 2
    ), st_cp

    # forged late arrival: the compromised straggler tampers its rows
    # after the digest — repair_round must exclude it with evidence,
    # and the degraded close's broadcast stands
    rng = np.random.default_rng(args.seed)
    grads = {c: rng.normal(size=dim).astype(np.float32) for c in clients}
    co = ShardedCoordinator(
        mk_tenants(), n_shards, quorum=2, repair_horizon_rounds=2
    )
    co.shards[straggler] = CompromisedShard(
        co.shards[straggler], mode="bitflip", seed=args.seed,
        n_shards=n_shards,
    )
    forged_rejected = 0
    for r in range(rounds):
        for c in clients:
            ok, _ = co.submit("m0", c, r, grads[c], seq=r)
            assert ok
        late = co.shards[straggler].close_partial("m0")
        assert late is not None
        present = [
            co.shards[s].close_partial("m0")
            for s in range(n_shards)
            if s != straggler
        ]
        res = co.merge_partials(
            "m0", [p for p in present if p is not None],
            missing=[straggler],
        )
        assert res is not None, r
        before = np.asarray(res[2]).copy()
        rep = co.repair_round("m0", late)
        assert rep is None, f"forged late partial folded at round {r}"
        forged_rejected += 1
        rt_last = co._roots["m0"].last_aggregate
        assert np.array_equal(np.asarray(rt_last), before), r
    events = [
        e for e in co.shard_events if e["event"] == "shard_forged"
    ]
    assert len(events) == rounds and all(
        e["shard"] == straggler for e in events
    ), events

    row = {
        "lane": "speculative",
        "aggregator": "multi-krum",
        "clients": n_clients,
        "shards": n_shards,
        "rounds": rounds,
        "seeds": len(seeds),
        "repair_parity_rounds": parity_rounds,
        "repair_parity": "bit-identical",
        "streaming_repair_rounds": streaming_repair_rounds,
        "streaming_repair_parity": "bit-identical",
        "streaming_repair_verify_cost": "arrival-cached",
        "closepath_staged_closes": st_cp["staged_closes"],
        "closepath_partial_transforms": st_cp["partial_transforms"],
        "closepath_gram_cross_blocks": st_cp["gram_cross_blocks"],
        "replay_rejected": "all",
        "forged_late_rejected": forged_rejected,
        "evidence_events": len(events),
    }
    _emit(row, out)
    return row


def run_swarm(args, out) -> dict:
    scenario = Scenario(
        name="swarm",
        seed=args.seed,
        n_clients=args.clients_swarm,
        n_byzantine=max(1, args.clients_swarm // 100),
        dim=args.dim,
        rounds=args.swarm_rounds,
        engine="serving",
        aggregator="trimmed_mean",
        aggregator_params={"f": max(1, args.clients_swarm // 100)},
        attack=AttackSpec(
            name="staleness_abuse",
            params={"kind": "exponential", "gamma": 0.5, "cutoff": 4},
        ),
        arrivals=ArrivalModel(kind="bernoulli", p=0.5),
        faults=FaultPlan(
            stragglers=StragglerModel(kind="bimodal", tail_prob=0.1),
            crash=CrashModel(prob_per_round=0.001, restart_after_rounds=3),
            partitions=(
                PartitionEvent(
                    start_round=args.swarm_rounds // 3,
                    end_round=2 * args.swarm_rounds // 3,
                    fraction=0.1,
                ),
            ),
        ),
        staleness_kind="exponential",
        staleness_gamma=0.5,
        staleness_cutoff=4,
        credit_rate_per_s=200.0,
        credit_burst=8.0,
    )
    t0 = time.monotonic()
    report = ChaosHarness(scenario).run()
    elapsed = time.monotonic() - t0
    submitted = sum(report.verdict_counts.values())
    # the actor-fabric twin: the same population through the real
    # actor-mode ParameterServer round loop (asyncio fan-out per node,
    # adaptive byzantine nodes on the observation channel) — the
    # Podracer claim that simulated thousands are cheap on BOTH fabrics
    actor = ChaosHarness(
        scenario.with_(
            name="swarm-actor",
            engine="actor",
            n_clients=args.clients_actor,
            n_byzantine=max(1, args.clients_actor // 100),
            aggregator_params={"f": max(1, args.clients_actor // 100)},
            rounds=max(3, args.swarm_rounds // 3),
            attack=AttackSpec(
                name="influence_ascent", params={"grow": 1.8, "scale0": 0.1}
            ),
            faults=FaultPlan(),
            arrivals=ArrivalModel(),
        )
    )
    ta = time.monotonic()
    actor_report = actor.run()
    actor_elapsed = time.monotonic() - ta
    actor_row = {
        "lane": "swarm_actor",
        "clients": args.clients_actor,
        "rounds": actor_report.rounds_completed,
        "wall_s": round(actor_elapsed, 3),
        "gradients_per_sec": round(
            args.clients_actor
            * actor_report.rounds_completed
            / max(actor_elapsed, 1e-9),
            1,
        ),
        # no influence metric here: the actor engine publishes only what
        # the real PS publishes (the aggregate), and the leave-out
        # reference needs the cohort matrix the PS never exposes
        "final_error": round(actor_report.final_error, 6),
    }
    _emit(actor_row, out)
    row = {
        "lane": "swarm",
        "clients": scenario.n_clients,
        "byzantine": scenario.n_byzantine,
        "rounds": report.rounds_completed,
        "wall_s": round(elapsed, 3),
        "submissions": submitted,
        "submissions_per_sec": round(submitted / max(elapsed, 1e-9), 1),
        "verdicts": dict(report.verdict_counts),
        "events": report.trace.counts(),
        "final_error": round(report.final_error, 6),
        "influence_mean": round(report.influence_mean, 6),
        "trace_digest": report.trace.digest(),
    }
    _emit(row, out)
    return row


#: Sub-int8 fabric precisions the adversarial-residual lane drives
#: (ISSUE 15); the attack shapes the matching integer grid (s4 on the
#: s4 fabric, the 8-bit grid on fp8 — fp8 shaping is the same
#: scale-inflation signature).
SUBINT8_PRECISIONS = ("fp8", "s4")
SUBINT8_FLOOR_MODES = ("int8", "fp8", "fp8_e5m2", "s4")


def _subint8_floor_rows(args, out) -> list:
    """Precision floor per aggregator x attack: how far each wire mode's
    quantization error sits below the Byzantine perturbation the
    aggregator already tolerates (the PR-3 robustness-study rule,
    extended down the precision ladder). ``margin`` = tolerance / wire
    error; the floor DIES where margin < 1 — that boundary is the lane's
    deliverable, not an assertion."""
    import jax
    import jax.numpy as jnp

    from byzpy_tpu.ops import attack_ops, robust
    from byzpy_tpu.parallel import quantization as qz

    n, f = args.clients_grid * 2, args.byzantine
    d = 2048 if not args.smoke else 512
    aggs = {
        "trimmed_mean": partial(robust.trimmed_mean, f=f),
        "multi_krum": partial(robust.multi_krum, f=f, q=n - f - 2),
        "cge": partial(robust.cge, f=f),
    }
    key = jax.random.PRNGKey(args.seed)
    k1, k2, kg = jax.random.split(key, 3)
    signal = jax.random.normal(kg, (1, d), jnp.float32)
    x_clean = signal + jax.random.normal(k1, (n, d), jnp.float32)
    x_clean2 = signal + jax.random.normal(k2, (n, d), jnp.float32)

    def attacked(kind):
        honest = x_clean[: n - f]
        if kind == "empire":
            vec = attack_ops.empire(honest, scale=-1.1)
        elif kind == "little":
            vec = attack_ops.little(honest, f=f, n_total=n)
        else:
            vec = attack_ops.sign_flip(jnp.mean(honest, axis=0), scale=-4.0)
        return jnp.concatenate(
            [honest, jnp.broadcast_to(vec, (f, d)).astype(honest.dtype)],
            axis=0,
        )

    rows = []
    for agg_name, agg in aggs.items():
        agg_j = jax.jit(agg)
        base_clean = agg_j(x_clean)
        resample = float(jnp.linalg.norm(agg_j(x_clean2) - base_clean))
        for att in ("sign_flip", "little", "empire"):
            x_att = attacked(att)
            base_att = agg_j(x_att)
            tolerance = max(
                float(jnp.linalg.norm(base_att - base_clean)), resample
            )
            margins = {}
            floor = None
            floor_open = True
            for mode in SUBINT8_FLOOR_MODES:
                wire = qz.dequantize_blockwise(qz.encode_blockwise(x_att, mode))
                err = float(jnp.linalg.norm(agg_j(wire) - base_att))
                margin = tolerance / err if err > 0 else float("inf")
                margins[mode] = round(margin, 3)
                # the floor is the coarsest rung reachable WITHOUT
                # crossing a failed finer rung (the ladder's error
                # bounds overlap — e5m2 and s4 share absmax/14 — so a
                # non-monotone pass past a failure must not relabel
                # the failed rung as safe); boundary rule margin >= 1
                # == the robustness study's err/tolerance <= 1
                if floor_open and margin >= 1.0:
                    floor = mode
                else:
                    floor_open = False
            row = {
                "lane": "subint8_floor",
                "aggregator": agg_name,
                "attack": att,
                "n": n, "d": d, "f": f,
                "tolerance": round(tolerance, 6),
                "margin_by_mode": margins,
                "floor": floor,
            }
            rows.append(row)
            _emit(row, out)
    return rows


def run_sanitize(args, out) -> dict:
    """Runtime-sanitizer lane (ISSUE 20): one serving-engine cell runs
    twice — ``byzpy_tpu.analysis.sanitize`` hooks off, then on — and
    the sanitized run must (a) record ZERO invariant violations, (b)
    actually exercise the exactly-once fold audit (nonzero counters —
    a leg that never audited proves nothing), and (c) leave the
    event-trace digest and final error bit-identical to the
    unsanitized twin: the sanitizer is a pure observer, like the
    forensics plane before it."""
    from byzpy_tpu.analysis import sanitize

    cell = Scenario(
        name="sanitize-parity",
        seed=args.seed,
        n_clients=args.clients_grid,
        n_byzantine=args.byzantine,
        dim=args.dim,
        rounds=args.rounds,
        aggregator="trimmed_mean",
        aggregator_params={"f": 3},
        attack=AttackSpec(name="influence_ascent"),
        engine="serving",
    )
    plain = ChaosHarness(cell).run()
    was_enabled = sanitize.enabled()
    sanitize.enable()
    sanitize.reset()
    try:
        sanitized = ChaosHarness(cell).run()
        violations = sanitize.violations()
        counters = sanitize.counters()
    finally:
        if not was_enabled:
            sanitize.disable()
        sanitize.reset()
    row = {
        "lane": "sanitize",
        "engine": cell.engine,
        "rounds": sanitized.rounds_completed,
        "digest_parity": (
            sanitized.trace.digest() == plain.trace.digest()
            and sanitized.final_error == plain.final_error
        ),
        "violations": violations,
        "folds_audited": counters["folds_audited"],
        "loop_ticks": counters["loop_ticks"],
        "drain_checks": counters["drain_checks"],
    }
    _emit(row, out)
    return row


def run_subint8(args, out) -> dict:
    """Adversarial-residual lane (ISSUE 15): the residual-shaping
    attacker — an encoder-controlling client steering its own sub-int8
    quantization error through error feedback — driven through the REAL
    serving admission path per aggregator x fabric precision, measured
    for influence against its unshaped (influence-ascent) twin, and
    screened by the forensics ``residual_shaping`` detector with the
    honest false-positive rate pinned; plus the per-aggregator
    precision-floor table."""
    fc = _forensics_config()
    rows = []
    for agg_name, agg_params in args.aggregators:
        for prec in SUBINT8_PRECISIONS:
            shape_mode = "s4" if prec == "s4" else "int8"
            common = dict(
                seed=args.seed,
                n_clients=args.clients_grid,
                dim=args.dim,
                rounds=args.rounds,
                aggregator=agg_name,
                aggregator_params=agg_params,
                engine="serving",
                precision=prec,
            )
            baseline = ChaosHarness(
                Scenario(name=f"subint8-baseline/{agg_name}/{prec}", **common)
            ).run()
            cell = Scenario(
                name=f"subint8/{agg_name}/{prec}",
                n_byzantine=args.byzantine,
                attack=AttackSpec(
                    name="residual_shaping",
                    params={"mode": shape_mode, "kappa": 4.0,
                            "scale0": 0.05},
                ),
                **common,
            )
            report = ChaosHarness(cell, forensics=fc).run()
            s = report.forensics_summary()
            plain = ChaosHarness(
                Scenario(
                    name=f"subint8-plain/{agg_name}/{prec}",
                    n_byzantine=args.byzantine,
                    attack=AttackSpec(
                        name="influence_ascent", params={"scale0": 0.05}
                    ),
                    **common,
                )
            ).run()
            row = {
                "lane": "subint8",
                "aggregator": agg_name,
                "precision": prec,
                "attack": "residual_shaping",
                "shape_mode": shape_mode,
                "kappa": 4.0,
                "rounds": report.rounds_completed,
                "mean_influence": round(report.influence_mean, 6),
                "max_influence": round(report.influence_max, 6),
                "plain_mean_influence": round(plain.influence_mean, 6),
                "shaping_vs_plain": round(
                    report.influence_mean / max(plain.influence_mean, 1e-9), 3
                ),
                "final_error": round(report.final_error, 6),
                "baseline_error": round(baseline.final_error, 6),
                "verdict": _verdict(report.final_error, baseline.final_error),
                "byz_present": s["byz_present"],
                "byz_flagged": s["byz_flagged"],
                "recall": s["recall"],
                "first_byz_flag_round": s["first_byz_flag_round"],
                "honest_fp_rate": round(s["honest_fp_rate"], 4),
                "flags_by_detector": s["flags_by_detector"],
                "within_budget": (
                    s["first_byz_flag_round"] is not None
                    and s["first_byz_flag_round"] <= DETECT_BUDGET
                ),
                "trace_digest": report.trace.digest(),
            }
            rows.append(row)
            _emit(row, out)
    # honest-only FP pin on the sub-int8 fabrics (every honest frame's
    # pre-decode inflation is exactly 1.0 — the detector must be silent)
    worst_fp = 0.0
    honest_runs = 0
    for i in range(min(args.forensics_honest_seeds, 3)):
        for prec in SUBINT8_PRECISIONS:
            cell = Scenario(
                name=f"subint8-honest/{prec}",
                seed=args.seed + i,
                n_clients=args.clients_grid,
                dim=args.dim,
                rounds=args.rounds,
                aggregator="trimmed_mean",
                aggregator_params={"f": args.byzantine},
                engine="serving",
                precision=prec,
            )
            s = ChaosHarness(cell, forensics=fc).run().forensics_summary()
            worst_fp = max(worst_fp, s["honest_fp_rate"])
            honest_runs += 1
    floor_rows = _subint8_floor_rows(args, out)
    summary = {
        "lane": "subint8_summary",
        "cells": len(rows),
        "shaping_all_flagged": all(
            r["byz_flagged"] == r["byz_present"] for r in rows
        ),
        "shaping_within_budget": all(r["within_budget"] for r in rows),
        "residual_shaping_fired": all(
            r["flags_by_detector"].get("residual_shaping", 0) > 0
            for r in rows
        ),
        "honest_runs": honest_runs,
        "honest_worst_fp_rate": round(worst_fp, 4),
        "fp_within_bound": worst_fp <= FP_BOUND,
        "floor_cells": len(floor_rows),
        "int8_floor_clean": all(
            r["margin_by_mode"]["int8"] >= 1.0 for r in floor_rows
        ),
        "floor_by_aggregator": {
            a: sorted(
                {
                    r["floor"]
                    for r in floor_rows
                    if r["aggregator"] == a and r["floor"] is not None
                }
            )
            for a in {r["aggregator"] for r in floor_rows}
        },
    }
    _emit(summary, out)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=20260804)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--clients-grid", type=int, default=12)
    ap.add_argument("--byzantine", type=int, default=3)
    ap.add_argument("--clients-swarm", type=int, default=3000)
    ap.add_argument("--clients-actor", type=int, default=1000)
    ap.add_argument("--swarm-rounds", type=int, default=12)
    ap.add_argument("--recovery-runs", type=int, default=20)
    ap.add_argument(
        "--forensics-honest-seeds", type=int, default=5,
        help="honest-only seeds per config for the FP-rate pin",
    )
    ap.add_argument(
        "--lanes", type=str,
        default=(
            "grid,adaptive,serving,swarm,recovery,forensics,ragged,shard,"
            "speculative,subint8,sanitize"
        ),
        help="comma-separated lane subset",
    )
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run with contract assertions")
    args = ap.parse_args()

    args.attacks = ATTACK_CELLS
    args.faults = list(FAULT_CELLS)
    args.aggregators = AGG_CELLS
    args.forensics_cells = FORENSICS_CELLS
    args.honest_configs = HONEST_CONFIGS
    if args.smoke:
        args.rounds = 10
        args.dim = 32
        args.clients_swarm = 400
        args.clients_actor = 120
        args.swarm_rounds = 6
        args.recovery_runs = 2
        args.attacks = [ATTACK_CELLS[0], ATTACK_CELLS[4]]
        args.faults = ["none", "crash_restart"]
        args.aggregators = AGG_CELLS[:2]
        # keep every ADAPTIVE forensics cell (the smoke's whole point is
        # "each adaptive attacker gets flagged"); drop the static extras
        args.forensics_cells = [c for c in FORENSICS_CELLS if c[5]]
        args.forensics_honest_seeds = 2
        args.honest_configs = HONEST_CONFIGS[:2] + HONEST_CONFIGS[3:]
    lanes = {s.strip() for s in args.lanes.split(",") if s.strip()}

    meta = {
        "lane": "meta",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "seed": args.seed,
        "smoke": bool(args.smoke),
    }
    _emit(meta, args.out)

    grid = run_grid(args, args.out) if "grid" in lanes else []
    adaptive = run_adaptive(args, args.out) if "adaptive" in lanes else []
    serving = run_serving(args, args.out) if "serving" in lanes else []
    swarm = run_swarm(args, args.out) if "swarm" in lanes else None
    recovery = run_recovery(args, args.out) if "recovery" in lanes else None
    forensics = run_forensics(args, args.out) if "forensics" in lanes else None
    ragged = run_ragged(args, args.out) if "ragged" in lanes else None
    shard = run_shard(args, args.out) if "shard" in lanes else None
    speculative = (
        run_speculative(args, args.out) if "speculative" in lanes else None
    )
    subint8 = run_subint8(args, args.out) if "subint8" in lanes else None
    sanitized = run_sanitize(args, args.out) if "sanitize" in lanes else None

    crashed = [r for r in grid if r.get("harness_crashed")]
    headline = {
        "lane": "headline",
        "metric": "chaos_grid_cells",
        "value": len(grid),
        "crashed_cells": len(crashed),
        "breached_cells": sum(
            1 for r in grid if r.get("verdict") == "breached"
        ),
        "adaptive_beats_static": sum(
            1 for r in adaptive if r["adaptive_beats_static"]
        ),
        "serving_abuse_outcomes": {
            r["aggregator"]: r["outcome"] for r in serving
        },
        "swarm_submissions_per_sec": (
            swarm["submissions_per_sec"] if swarm else None
        ),
        "recovery_violations": (
            recovery["kill_violations"] + recovery["wire_violations"]
            if recovery
            else None
        ),
        "forensics_adaptive_within_budget": (
            forensics["adaptive_within_budget"] if forensics else None
        ),
        "forensics_honest_worst_fp": (
            forensics["honest_worst_fp_rate"] if forensics else None
        ),
        "ragged_door_digest_match": (
            ragged["digest_match"] if ragged else None
        ),
        "shard_forged_detected": (
            {k: v["forged_detected"] for k, v in shard["forgery"].items()}
            if shard
            else None
        ),
        "speculative_repair_parity": (
            speculative["repair_parity"] if speculative else None
        ),
        "subint8_shaping_flagged": (
            subint8["shaping_all_flagged"] if subint8 else None
        ),
        "subint8_honest_worst_fp": (
            subint8["honest_worst_fp_rate"] if subint8 else None
        ),
        "subint8_floor_by_aggregator": (
            subint8["floor_by_aggregator"] if subint8 else None
        ),
        "sanitize_digest_parity": (
            sanitized["digest_parity"] if sanitized else None
        ),
    }
    _emit(headline, args.out)

    if args.smoke and recovery is not None:
        assert recovery["kill_violations"] == 0, recovery
        assert recovery["wire_violations"] == 0, recovery
        assert recovery["recovery_metric_exported"], recovery
    if args.smoke and "adaptive" in lanes:
        assert headline["adaptive_beats_static"] >= 1, (
            "no adaptive attacker beat its static counterpart"
        )
    if args.smoke and "grid" in lanes:
        assert not crashed, f"harness-crashed cells: {crashed}"
        # replay determinism: rerun one cell, digests must match
        cell = Scenario(
            name="smoke-replay",
            seed=args.seed,
            n_clients=args.clients_grid,
            n_byzantine=args.byzantine,
            dim=args.dim,
            rounds=args.rounds,
            aggregator="trimmed_mean",
            aggregator_params={"f": 3},
            attack=AttackSpec(name="influence_ascent"),
            faults=FAULT_CELLS["crash_restart"],
        )
        d1 = ChaosHarness(cell).run().trace.digest()
        d2 = ChaosHarness(cell).run().trace.digest()
        assert d1 == d2, "chaos cell not replayable"
    if args.smoke and swarm is not None:
        assert swarm["rounds"] > 0 and swarm["submissions"] > 0
    if args.smoke and shard is not None:
        # run_shard asserts parity + detection internally; pin the
        # headline shape so a silently-skipped lane can't look green
        assert shard["parity"] == "bit-identical", shard
        assert all(
            v["forged_detected"] == v["rounds"]
            for v in shard["forgery"].values()
        ), shard
        # streaming root merge (ISSUE 18) must not move a single digit
        # of the lane: arrival-driven verify+fold digest-equal to the
        # barrier path, forgery detection rate unchanged
        assert shard["streaming_parity"] == "bit-identical", shard
        assert all(
            v["streaming_forged_detected"] == v["rounds"]
            for v in shard["forgery"].values()
        ), shard
    if args.smoke and speculative is not None:
        # run_speculative asserts repair parity + replay/forgery
        # rejection internally; pin the headline shape here too
        assert speculative["repair_parity"] == "bit-identical", speculative
        assert speculative["repair_parity_rounds"] > 0, speculative
        assert (
            speculative["forged_late_rejected"] == speculative["rounds"]
        ), speculative
        # streaming composes with the speculative close: the repair
        # reuses the arrival-time verify and stays bit-identical
        assert (
            speculative["streaming_repair_parity"] == "bit-identical"
        ), speculative
        assert (
            speculative["streaming_repair_rounds"]
            == speculative["rounds"]
        ), speculative
    if args.smoke and subint8 is not None:
        assert subint8["shaping_all_flagged"], subint8
        assert subint8["residual_shaping_fired"], subint8
        assert subint8["fp_within_bound"], subint8
        assert subint8["int8_floor_clean"], subint8
    if args.smoke and sanitized is not None:
        # the sanitizer is a pure observer with teeth: bit-identical
        # digests, zero violations, and the audits really ran
        assert sanitized["digest_parity"], sanitized
        assert sanitized["violations"] == [], sanitized
        assert sanitized["folds_audited"] > 0, sanitized
    if args.smoke and forensics is not None:
        assert forensics["adaptive_all_flagged"], forensics
        assert forensics["adaptive_within_budget"], forensics
        assert forensics["fp_within_bound"], forensics
        assert forensics["digest_parity"], forensics
        assert forensics["wal_audit_ok"], forensics
        assert forensics["prometheus_ok"], forensics
    if args.smoke:
        print("chaos smoke OK")


if __name__ == "__main__":
    main()
