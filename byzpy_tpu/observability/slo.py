"""SLO watchdog: declarative per-tenant objectives, burn rates, alarms.

The serving tier publishes admission/round/forensics metrics (PR 8);
this module turns them into the machine-readable health signal the
shard-autoscaling roadmap item will consume. An operator declares
per-tenant objectives — accepted-round p99 latency, failed-round rate,
quarantine rate — and a :class:`SLOWatchdog` evaluates them as
**rolling-window burn rates** off the existing metrics registry: each
``evaluate()`` snapshots the tenant's counters/histograms, diffs them
against the snapshot at the window's far edge, and computes

``burn = (bad fraction in the window) / (objective's error budget)``

so ``burn == 1.0`` means "exactly eating the budget", ``> threshold``
is a breach. Evaluation publishes ``byzpy_slo_*`` metrics on the same
Prometheus scrape as everything else, mirrors each breach transition
onto the tracer as an ``slo.breach`` instant (it lands inside whatever
span is open, linking alarms into round trees), and — when a flight
path is configured — triggers a flight-recorder dump whose trailing
rounds and critical-path summaries show what the tier was doing as the
budget burned.

Clock-agnostic: pass ``clock=`` to evaluate on a virtual clock — the
chaos harness drives a watchdog on its deterministic virtual time, so
SLO behavior under injected faults is replayable (and digests stay
untouched: the watchdog only ever reads).
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import metrics as _metrics
from . import tracing as _tracing

#: Watchdogs currently alive in this process — the flight recorder
#: embeds their state in crash dumps without holding them alive.
_ACTIVE: "weakref.WeakSet[SLOWatchdog]" = weakref.WeakSet()


@dataclass(frozen=True)
class BurnRatePolicy:
    """Multiwindow burn-rate alerting pair (the SRE-workbook
    convention): an objective breaches only
    when the burn exceeds ``burn_threshold`` over BOTH the short and
    the long window — the long window proves the budget spend is
    significant, the short window proves it is still happening (no
    paging on a spike that already ended, no paging hours late on a
    slow leak). Two presets carry the conventional thresholds:

    * :meth:`page` — fast burn, ~14× budget over (5 min, 1 h): at that
      rate a 30-day budget dies in ~2 days, someone should wake up;
    * :meth:`ticket` — slow burn, ~3× (1–6× family) over (30 min,
      6 h): worth a ticket, not a page.

    The single-window fields on :class:`TenantSLO` (``window_s`` +
    ``burn_threshold``) stay the default and are byte-for-byte
    unchanged when no policy is attached; the autoscaler keeps reading
    ``byzpy_slo_burn_rate`` either way (it carries the LONG-window
    burn under a policy — the budget-significant signal — with the
    short window published alongside as
    ``byzpy_slo_short_burn_rate``)."""

    short_window_s: float
    long_window_s: float
    burn_threshold: float
    severity: str = "page"

    def __post_init__(self) -> None:
        if not 0 < self.short_window_s <= self.long_window_s:
            raise ValueError(
                "need 0 < short_window_s <= long_window_s "
                f"(got {self.short_window_s}/{self.long_window_s})"
            )
        if self.burn_threshold <= 0:
            raise ValueError("burn_threshold must be > 0")

    @classmethod
    def page(
        cls,
        *,
        short_window_s: float = 300.0,
        long_window_s: float = 3600.0,
        burn_threshold: float = 14.0,
    ) -> "BurnRatePolicy":
        """Page-severity preset: ~14× burn over (5 min, 1 h)."""
        return cls(
            short_window_s=short_window_s,
            long_window_s=long_window_s,
            burn_threshold=burn_threshold,
            severity="page",
        )

    @classmethod
    def ticket(
        cls,
        *,
        short_window_s: float = 1800.0,
        long_window_s: float = 21600.0,
        burn_threshold: float = 3.0,
    ) -> "BurnRatePolicy":
        """Ticket-severity preset: ~3× burn over (30 min, 6 h)."""
        return cls(
            short_window_s=short_window_s,
            long_window_s=long_window_s,
            burn_threshold=burn_threshold,
            severity="ticket",
        )


@dataclass(frozen=True)
class TenantSLO:
    """Declarative objectives for one serving tenant.

    ``accepted_p99_s``: closed rounds should finish within this many
    seconds at the 99th percentile — evaluated as "≤ 1% of the
    window's rounds may exceed it" (the 1% IS the error budget).
    ``failed_round_rate``: max fraction of round closes the crash
    guard may drop. ``quarantine_rate``: max fraction of admission
    verdicts that are quarantine/trust rejections. ``None`` disables
    an objective. ``window_s`` is the rolling evaluation window;
    ``burn_threshold`` the burn rate that counts as a breach (1.0 =
    alarm exactly at budget). Attach a :class:`BurnRatePolicy` as
    ``burn`` for multiwindow page/ticket alerting — the single-window
    fields are then ignored in favor of the policy's (short, long)
    pair."""

    tenant: str
    accepted_p99_s: Optional[float] = None
    failed_round_rate: Optional[float] = None
    quarantine_rate: Optional[float] = None
    window_s: float = 60.0
    burn_threshold: float = 1.0
    burn: Optional[BurnRatePolicy] = None

    def objectives(self) -> List[str]:
        """The objective names this SLO activates."""
        out = []
        if self.accepted_p99_s is not None:
            out.append("accepted_p99")
        if self.failed_round_rate is not None:
            out.append("failed_rounds")
        if self.quarantine_rate is not None:
            out.append("quarantine")
        return out


#: Error budget of the latency objective: p99 ⇒ 1% of rounds may be
#: slower than the target.
_LATENCY_BUDGET = 0.01

#: Admission outcomes counted against the quarantine objective.
_QUARANTINE_OUTCOMES = ("rejected_quarantined", "rejected_untrusted")


def _hist_over(
    buckets: Sequence[float], counts: Sequence[int], target: float
) -> Tuple[int, int]:
    """(samples over ``target``, total samples) from one histogram
    state, interpolating inside the bucket the target falls in (the
    same bounded-error rule ``Histogram.percentile`` uses)."""
    total = int(sum(counts))
    if total == 0:
        return 0, 0
    over = int(counts[-1])  # +Inf bin is always over any finite target
    for i, edge in enumerate(buckets):
        if edge <= target:
            continue
        lo = buckets[i - 1] if i > 0 else 0.0
        inside = int(counts[i])
        frac_over = (edge - target) / (edge - lo) if edge > lo else 0.0
        over += int(round(inside * frac_over))
        over += int(sum(counts[i + 1:-1]))
        break
    return over, total


@dataclass
class _Snapshot:
    """Counter/histogram state at one evaluation instant."""

    t: float
    rounds: float = 0.0
    failed: float = 0.0
    verdicts_total: float = 0.0
    quarantined: float = 0.0
    latency_counts: Tuple[int, ...] = ()


@dataclass
class _ObjectiveState:
    """Rolling state of one (tenant, objective) pair."""

    breached: bool = False
    breaches: int = 0
    burn: float = 0.0
    bad: int = 0
    total: int = 0
    #: short-window burn (multiwindow policies only; 0.0 otherwise)
    short_burn: float = 0.0


class SLOWatchdog:
    """Evaluates a set of :class:`TenantSLO`\\ s against the registry.

    Construct once per process (it registers gauges/counters under
    ``byzpy_slo_*``), then call :meth:`evaluate` on whatever cadence
    the deployment likes — the serving scheduler's window, a cron, or
    the chaos harness's virtual round clock. Evaluation is pure
    reading plus its own metric publishing: it never perturbs round
    arithmetic, digests, or admission state."""

    def __init__(
        self,
        slos: Sequence[TenantSLO],
        *,
        registry: Optional[_metrics.MetricsRegistry] = None,
        clock: Callable[[], float] = time.monotonic,
        flight_path: Optional[str] = None,
        flight_recorder: Optional[Any] = None,
        on_breach: Optional[Callable[[str, str, dict], None]] = None,
    ) -> None:
        if not slos:
            raise ValueError("at least one TenantSLO is required")
        tenants = [slo.tenant for slo in slos]
        if len(set(tenants)) != len(tenants):
            # one TenantSLO per tenant: the rolling snapshot history is
            # per-tenant, so two SLOs with different windows would pop
            # each other's snapshots (and their byzpy_slo_* series
            # would collide) — declare all of a tenant's objectives on
            # ONE TenantSLO
            dupes = sorted({t for t in tenants if tenants.count(t) > 1})
            raise ValueError(
                f"duplicate TenantSLO for tenant(s) {dupes}: declare all "
                "of a tenant's objectives on one TenantSLO"
            )
        self.slos = list(slos)
        self.registry = registry or _metrics.registry()
        self.clock = clock
        self.flight_path = flight_path
        self._recorder = flight_recorder
        self._on_breach = on_breach
        self.flight_dumps = 0
        self._history: Dict[str, "deque[_Snapshot]"] = {
            slo.tenant: deque() for slo in self.slos
        }
        self._state: Dict[Tuple[str, str], _ObjectiveState] = {}
        self._gauges: Dict[Tuple[str, str, str], Any] = {}
        reg = self.registry
        for slo in self.slos:
            for obj in slo.objectives():
                labels = {"tenant": slo.tenant, "objective": obj}
                self._state[(slo.tenant, obj)] = _ObjectiveState()
                self._gauges[(slo.tenant, obj, "burn")] = reg.gauge(
                    "byzpy_slo_burn_rate",
                    help=(
                        "rolling-window error-budget burn rate "
                        "(1.0 = exactly at budget)"
                    ),
                    labels=labels,
                )
                self._gauges[(slo.tenant, obj, "breached")] = reg.gauge(
                    "byzpy_slo_breached",
                    help="1 while the objective's burn exceeds its threshold",
                    labels=labels,
                )
                self._gauges[(slo.tenant, obj, "breaches")] = reg.counter(
                    "byzpy_slo_breaches_total",
                    help="ok->breached transitions",
                    labels=labels,
                )
                self._gauges[(slo.tenant, obj, "target")] = reg.gauge(
                    "byzpy_slo_objective_target",
                    help="declared objective target (seconds or fraction)",
                    labels=labels,
                )
                if slo.burn is not None:
                    self._gauges[(slo.tenant, obj, "short_burn")] = (
                        reg.gauge(
                            "byzpy_slo_short_burn_rate",
                            help=(
                                "short-window burn of a multiwindow "
                                "policy (byzpy_slo_burn_rate carries "
                                "the long window)"
                            ),
                            labels=labels,
                        )
                    )
            t = self._gauges
            if slo.accepted_p99_s is not None:
                t[(slo.tenant, "accepted_p99", "target")].set(
                    slo.accepted_p99_s
                )
            if slo.failed_round_rate is not None:
                t[(slo.tenant, "failed_rounds", "target")].set(
                    slo.failed_round_rate
                )
            if slo.quarantine_rate is not None:
                t[(slo.tenant, "quarantine", "target")].set(
                    slo.quarantine_rate
                )
        # prime each tenant's window with the construction-time state:
        # the watchdog scores what happened on ITS watch, not counter
        # history from before it existed
        for slo in self.slos:
            self._history[slo.tenant].append(self._snapshot(slo.tenant))
        _ACTIVE.add(self)

    # -- reading the registry ---------------------------------------------

    def _snapshot(self, tenant: str) -> _Snapshot:
        reg = self.registry
        snap = _Snapshot(t=self.clock())
        snap.rounds = reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": tenant}
        ).value
        snap.failed = reg.counter(
            "byzpy_serving_failed_rounds_total", labels={"tenant": tenant}
        ).value
        hist = reg.histogram(
            "byzpy_serving_round_latency_seconds", labels={"tenant": tenant}
        )
        snap.latency_counts = tuple(hist.counts)
        verdicts_total = 0.0
        quarantined = 0.0
        for inst in reg.collect():
            if inst.name != "byzpy_serving_submissions_total":
                continue
            labels = inst.labels
            if labels.get("tenant") != tenant:
                continue
            verdicts_total += inst.value
            if labels.get("outcome") in _QUARANTINE_OUTCOMES:
                quarantined += inst.value
        snap.verdicts_total = verdicts_total
        snap.quarantined = quarantined
        return snap

    def _window_base(
        self, tenant: str, window_s: float, now: float, *, prune: bool
    ) -> _Snapshot:
        """The snapshot at the far edge of a rolling window (or the
        oldest retained — a young watchdog evaluates over what it
        has). ``prune=True`` drops history older than the window; a
        multiwindow pass prunes only for its LONG window and reads the
        short edge non-destructively."""
        hist = self._history[tenant]
        if prune:
            while len(hist) > 1 and hist[1].t <= now - window_s:
                hist.popleft()
        base = hist[0]
        for snap in hist:
            if snap.t <= now - window_s:
                base = snap
            else:
                break
        return base

    # -- evaluation --------------------------------------------------------

    def evaluate(self) -> List[dict]:
        """One evaluation pass over every declared objective; returns
        the per-objective rows (tenant, objective, burn, breached,
        window deltas). Publishes ``byzpy_slo_*``, emits ``slo.breach``
        tracer instants on ok→breached transitions, and triggers a
        flight dump on the first breach of a pass when a flight path /
        recorder is attached."""
        rows: List[dict] = []
        newly_breached: List[dict] = []
        for slo in self.slos:
            tenant = slo.tenant
            now = self.clock()
            cur = self._snapshot(tenant)
            if slo.burn is None:
                base = self._window_base(
                    tenant, slo.window_s, now, prune=True
                )
                for obj, bad, total, budget in self._objective_counts(
                    slo, cur, base
                ):
                    rows.append(
                        self._score(
                            slo, obj, bad, total, budget, newly_breached
                        )
                    )
            else:
                long_base = self._window_base(
                    tenant, slo.burn.long_window_s, now, prune=True
                )
                short_base = self._window_base(
                    tenant, slo.burn.short_window_s, now, prune=False
                )
                short = {
                    obj: (bad, total, budget)
                    for obj, bad, total, budget in self._objective_counts(
                        slo, cur, short_base
                    )
                }
                for obj, bad, total, budget in self._objective_counts(
                    slo, cur, long_base
                ):
                    s_bad, s_total, _b = short[obj]
                    rows.append(
                        self._score_multiwindow(
                            slo, obj, bad, total, s_bad, s_total,
                            budget, newly_breached,
                        )
                    )
            self._history[tenant].append(cur)
        if newly_breached:
            self._flight_dump(newly_breached)
        return rows

    def _objective_counts(
        self, slo: TenantSLO, cur: _Snapshot, base: _Snapshot
    ) -> List[Tuple[str, int, int, float]]:
        """Per-objective ``(name, bad, total, budget)`` counts over one
        window's delta — the shared middle of the single-window and
        multiwindow scorers."""
        out: List[Tuple[str, int, int, float]] = []
        if slo.accepted_p99_s is not None:
            counts = [
                int(c - b)
                for c, b in zip(
                    cur.latency_counts, base.latency_counts, strict=True
                )
            ]
            buckets = self.registry.histogram(
                "byzpy_serving_round_latency_seconds",
                labels={"tenant": slo.tenant},
            ).buckets
            over, total = _hist_over(buckets, counts, slo.accepted_p99_s)
            out.append(("accepted_p99", over, total, _LATENCY_BUDGET))
        if slo.failed_round_rate is not None:
            failed = cur.failed - base.failed
            closes = (cur.rounds - base.rounds) + failed
            out.append(
                (
                    "failed_rounds", int(failed), int(closes),
                    slo.failed_round_rate,
                )
            )
        if slo.quarantine_rate is not None:
            bad = cur.quarantined - base.quarantined
            total_v = cur.verdicts_total - base.verdicts_total
            out.append(
                ("quarantine", int(bad), int(total_v), slo.quarantine_rate)
            )
        return out

    def _score(
        self,
        slo: TenantSLO,
        objective: str,
        bad: int,
        total: int,
        budget: float,
        newly_breached: List[dict],
    ) -> dict:
        """Fold one (tenant, objective) window into burn/breach state
        and publish it."""
        state = self._state[(slo.tenant, objective)]
        bad_frac = (bad / total) if total > 0 else 0.0
        burn = bad_frac / budget if budget > 0 else 0.0
        breached = total > 0 and burn > slo.burn_threshold
        state.burn, state.bad, state.total = burn, bad, total
        self._gauges[(slo.tenant, objective, "burn")].set(burn)
        self._gauges[(slo.tenant, objective, "breached")].set(
            1.0 if breached else 0.0
        )
        row = {
            "tenant": slo.tenant,
            "objective": objective,
            "bad": bad,
            "total": total,
            "burn": round(burn, 4),
            "threshold": slo.burn_threshold,
            "breached": breached,
        }
        if breached and not state.breached:
            state.breaches += 1
            self._gauges[(slo.tenant, objective, "breaches")].inc()
            _tracing.instant(
                "slo.breach",
                track="slo",
                tenant=slo.tenant,
                objective=objective,
                burn=round(burn, 4),
                bad=bad,
                total=total,
            )
            newly_breached.append(row)
            if self._on_breach is not None:
                try:
                    self._on_breach(slo.tenant, objective, row)
                except Exception:  # noqa: BLE001 — observer bug, never
                    # the watchdog's outage
                    pass
        state.breached = breached
        return row

    def _score_multiwindow(
        self,
        slo: TenantSLO,
        objective: str,
        bad: int,
        total: int,
        short_bad: int,
        short_total: int,
        budget: float,
        newly_breached: List[dict],
    ) -> dict:
        """Multiwindow fold: burn over the long AND the short window,
        breach only when both exceed the policy threshold. The long
        window's burn is what ``byzpy_slo_burn_rate`` publishes (the
        budget-significant number the autoscaler reads); the short
        window rides ``byzpy_slo_short_burn_rate``."""
        policy = slo.burn
        assert policy is not None
        state = self._state[(slo.tenant, objective)]
        bad_frac = (bad / total) if total > 0 else 0.0
        burn = bad_frac / budget if budget > 0 else 0.0
        s_frac = (short_bad / short_total) if short_total > 0 else 0.0
        short_burn = s_frac / budget if budget > 0 else 0.0
        breached = (
            total > 0
            and short_total > 0
            and burn > policy.burn_threshold
            and short_burn > policy.burn_threshold
        )
        state.burn, state.bad, state.total = burn, bad, total
        state.short_burn = short_burn
        self._gauges[(slo.tenant, objective, "burn")].set(burn)
        self._gauges[(slo.tenant, objective, "short_burn")].set(short_burn)
        self._gauges[(slo.tenant, objective, "breached")].set(
            1.0 if breached else 0.0
        )
        row = {
            "tenant": slo.tenant,
            "objective": objective,
            "bad": bad,
            "total": total,
            "burn": round(burn, 4),
            "short_bad": short_bad,
            "short_total": short_total,
            "short_burn": round(short_burn, 4),
            "threshold": policy.burn_threshold,
            "severity": policy.severity,
            "breached": breached,
        }
        if breached and not state.breached:
            state.breaches += 1
            self._gauges[(slo.tenant, objective, "breaches")].inc()
            _tracing.instant(
                "slo.breach",
                track="slo",
                tenant=slo.tenant,
                objective=objective,
                severity=policy.severity,
                burn=round(burn, 4),
                short_burn=round(short_burn, 4),
                bad=bad,
                total=total,
            )
            newly_breached.append(row)
            if self._on_breach is not None:
                try:
                    self._on_breach(slo.tenant, objective, row)
                except Exception:  # noqa: BLE001 — observer bug, never
                    # the watchdog's outage
                    pass
        state.breached = breached
        return row

    def _flight_dump(self, breaches: List[dict]) -> None:
        """Dump the flight recorder on a fresh breach: the trailing
        rounds + critical-path + SLO state artifact an operator (or
        the autoscaler) reads to see what burned the budget."""
        if self.flight_path is None and self._recorder is None:
            return
        try:
            recorder = self._recorder
            if recorder is None:
                from .recorder import FlightRecorder

                recorder = FlightRecorder()
            b = breaches[0]
            reason = f"slo:{b['tenant']}:{b['objective']}"
            if self.flight_path is not None:
                recorder.dump(self.flight_path, reason=reason)
            else:
                recorder.record(reason)
            self.flight_dumps += 1
        except Exception:  # noqa: BLE001 — an alarm artifact must never
            # take down the plane it observes
            pass

    # -- introspection -----------------------------------------------------

    def state(self) -> dict:
        """JSON-ready burn/breach state per (tenant, objective) — the
        flight recorder embeds this in every dump."""
        return {
            "objectives": [
                {
                    "tenant": tenant,
                    "objective": objective,
                    "burn": round(st.burn, 4),
                    "short_burn": round(st.short_burn, 4),
                    "breached": st.breached,
                    "breaches": st.breaches,
                    "bad": st.bad,
                    "total": st.total,
                }
                for (tenant, objective), st in sorted(self._state.items())
            ],
            "flight_dumps": self.flight_dumps,
        }

    def close(self) -> None:
        """Deregister from the process-wide active set (dumps stop
        embedding this watchdog's state)."""
        _ACTIVE.discard(self)


def active_state() -> List[dict]:
    """Every live watchdog's :meth:`SLOWatchdog.state` (the flight
    recorder's source; empty when no watchdog is configured)."""
    return [w.state() for w in list(_ACTIVE)]


__all__ = ["BurnRatePolicy", "SLOWatchdog", "TenantSLO", "active_state"]
