"""SLO watchdog: burn-rate math, breach transitions, flight dumps.

Contracts under test:

* **burn rates are windowed** — objectives score counter/histogram
  DELTAS over the rolling window against the declared error budget
  (p99 latency ⇒ 1% budget); counts from before the watchdog existed
  or outside the window never count;
* **breach edges, not levels** — ``byzpy_slo_breaches_total`` counts
  ok→breached transitions once, the breach instant lands on the
  tracer, and recovery re-arms the edge;
* **the breach artifact** — a configured flight path gets a
  flight-recorder dump whose reason names the burned objective, and
  dumps embed every live watchdog's state + the tail rounds'
  critical-path summaries;
* **virtual clocks work** — the chaos harness's serving engine
  evaluates a ``Scenario.slo`` on virtual time with digests pinned
  identical SLO on/off.
"""

import json

import pytest

from byzpy_tpu import observability as obs
from byzpy_tpu.observability import metrics as obs_metrics
from byzpy_tpu.observability import tracing as obs_tracing
from byzpy_tpu.observability.slo import SLOWatchdog, TenantSLO, active_state


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    obs.disable()
    obs_tracing.tracer().clear()
    obs_tracing.adopt_context(None)
    yield
    obs.disable()
    obs_tracing.tracer().clear()
    obs_tracing.adopt_context(None)


def _registry(tenant="m0"):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("byzpy_serving_rounds_total", labels={"tenant": tenant})
    reg.counter("byzpy_serving_failed_rounds_total", labels={"tenant": tenant})
    reg.histogram(
        "byzpy_serving_round_latency_seconds", labels={"tenant": tenant}
    )
    return reg


class TestBurnRates:
    def test_latency_burn_and_breach(self):
        reg = _registry()
        clock = [0.0]
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", accepted_p99_s=0.1, window_s=10.0)],
            registry=reg, clock=lambda: clock[0],
        )
        h = reg.histogram(
            "byzpy_serving_round_latency_seconds", labels={"tenant": "m0"}
        )
        # all rounds inside budget: burn 0
        for _ in range(100):
            h.observe(0.01)
        clock[0] = 1.0
        (row,) = w.evaluate()
        assert row["burn"] == 0.0 and not row["breached"]
        # 10 of the window's 200 rounds over target (the 10 s window
        # still reaches back to construction): 5% over a 1% budget
        for _ in range(90):
            h.observe(0.01)
        for _ in range(10):
            h.observe(0.5)
        clock[0] = 2.0
        (row,) = w.evaluate()
        assert row["burn"] == pytest.approx(5.0, rel=0.1)
        assert row["breached"]

    def test_counts_before_construction_never_count(self):
        reg = _registry()
        h = reg.histogram(
            "byzpy_serving_round_latency_seconds", labels={"tenant": "m0"}
        )
        for _ in range(50):
            h.observe(9.0)  # terrible history, before the watchdog
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", accepted_p99_s=0.1)], registry=reg
        )
        (row,) = w.evaluate()
        assert row["total"] == 0 and row["burn"] == 0.0

    def test_window_expiry_forgets_old_badness(self):
        reg = _registry()
        clock = [0.0]
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", failed_round_rate=0.1, window_s=5.0)],
            registry=reg, clock=lambda: clock[0],
        )
        failed = reg.counter(
            "byzpy_serving_failed_rounds_total", labels={"tenant": "m0"}
        )
        rounds = reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": "m0"}
        )
        failed.inc(5)
        rounds.inc(5)
        clock[0] = 1.0
        (row,) = w.evaluate()
        assert row["breached"] and row["bad"] == 5
        # a clean stretch longer than the window: the old failures age out
        rounds.inc(50)
        for t in (3.0, 5.0, 7.0, 9.0):
            clock[0] = t
            (row,) = w.evaluate()
        assert not row["breached"] and row["bad"] == 0

    def test_quarantine_rate_objective(self):
        reg = _registry()
        acc = reg.counter(
            "byzpy_serving_submissions_total",
            labels={"tenant": "m0", "outcome": "accepted"},
        )
        quar = reg.counter(
            "byzpy_serving_submissions_total",
            labels={"tenant": "m0", "outcome": "rejected_untrusted"},
        )
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", quarantine_rate=0.2)], registry=reg
        )
        acc.inc(50)
        quar.inc(50)
        (row,) = w.evaluate()
        assert row["objective"] == "quarantine"
        assert row["burn"] == pytest.approx(0.5 / 0.2)
        assert row["breached"]

    def test_publishes_slo_metric_families(self):
        reg = _registry()
        w = SLOWatchdog(
            [
                TenantSLO(
                    tenant="m0", accepted_p99_s=0.5,
                    failed_round_rate=0.01, quarantine_rate=0.05,
                )
            ],
            registry=reg,
        )
        w.evaluate()
        text = reg.prometheus_text()
        for family in (
            "# TYPE byzpy_slo_burn_rate gauge",
            "# TYPE byzpy_slo_breached gauge",
            "# TYPE byzpy_slo_breaches_total counter",
            'byzpy_slo_objective_target{objective="accepted_p99",tenant="m0"} 0.5',
        ):
            assert family in text, family


class TestBreachEdges:
    def _breach_once(self, reg, clock):
        failed = reg.counter(
            "byzpy_serving_failed_rounds_total", labels={"tenant": "m0"}
        )
        reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": "m0"}
        ).inc(10)
        failed.inc(10)

    def test_transition_counts_once_and_rearms(self):
        obs.enable()
        reg = _registry()
        clock = [0.0]
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", failed_round_rate=0.1, window_s=4.0)],
            registry=reg, clock=lambda: clock[0],
        )
        breaches = reg.counter(
            "byzpy_slo_breaches_total",
            labels={"tenant": "m0", "objective": "failed_rounds"},
        )
        self._breach_once(reg, clock)
        clock[0] = 1.0
        w.evaluate()
        clock[0] = 2.0
        w.evaluate()  # still breached: level, not a second edge
        assert breaches.value == 1
        instants = [
            e for e in obs_tracing.tracer().events()
            if e["name"] == "slo.breach"
        ]
        assert len(instants) == 1
        assert instants[0]["args"]["objective"] == "failed_rounds"
        # recover (clean window), then breach again: second edge
        reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": "m0"}
        ).inc(100)
        for t in (5.0, 7.0, 9.0):
            clock[0] = t
            (row,) = w.evaluate()
        assert not row["breached"]
        self._breach_once(reg, clock)
        clock[0] = 10.0
        w.evaluate()
        assert breaches.value == 2

    def test_breach_triggers_flight_dump_with_reason(self, tmp_path):
        obs.enable()
        with obs_tracing.span("serving.round", round=0, tenant="m0"):
            pass
        reg = _registry()
        clock = [0.0]
        path = str(tmp_path / "slo_flight.json")
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", failed_round_rate=0.1)],
            registry=reg, clock=lambda: clock[0], flight_path=path,
        )
        self._breach_once(reg, clock)
        clock[0] = 1.0
        w.evaluate()
        assert w.flight_dumps == 1
        with open(path) as fh:
            dump = json.load(fh)
        assert dump["reason"] == "slo:m0:failed_rounds"
        assert dump["kind"] == "byzpy_tpu.flight_recorder"
        # the dump embeds the live watchdogs' state + critical path
        # (filtered by tenant: other tests' watchdogs may still be
        # alive in the weak set)
        ours = [
            o
            for s in dump["slo"]
            for o in s["objectives"]
            if o["tenant"] == "m0" and o["objective"] == "failed_rounds"
        ]
        assert any(o["breached"] for o in ours)
        assert dump["critical_path"]["rounds"], dump.get("critical_path")

    def test_on_breach_callback_is_crash_guarded(self):
        reg = _registry()
        clock = [0.0]
        seen = []

        def boom(tenant, objective, row):
            seen.append((tenant, objective))
            raise RuntimeError("observer bug")

        w = SLOWatchdog(
            [TenantSLO(tenant="m0", failed_round_rate=0.1)],
            registry=reg, clock=lambda: clock[0], on_breach=boom,
        )
        self._breach_once(reg, clock)
        clock[0] = 1.0
        w.evaluate()  # must not raise
        assert seen == [("m0", "failed_rounds")]


class TestRecorderEmbed:
    def test_active_state_and_close(self):
        reg = _registry("slo_embed_tenant")
        w = SLOWatchdog(
            [TenantSLO(tenant="slo_embed_tenant", failed_round_rate=0.5)],
            registry=reg,
        )
        w.evaluate()

        def listed():
            return any(
                o["tenant"] == "slo_embed_tenant"
                for s in active_state()
                for o in s["objectives"]
            )

        assert listed()
        w.close()
        assert not listed()


class TestChaosVirtualClock:
    def _scenario(self, slo):
        from byzpy_tpu.chaos import ArrivalModel, AttackSpec, Scenario

        return Scenario(
            name="slo", seed=9, n_clients=6, n_byzantine=1, dim=8,
            rounds=4, aggregator="trimmed_mean",
            aggregator_params={"f": 1},
            attack=AttackSpec(name="sign_flip"),
            arrivals=ArrivalModel(kind="bernoulli", p=0.9),
            engine="serving", slo=slo,
        )

    def test_virtual_clock_evaluation_and_digest_parity(self):
        from byzpy_tpu.chaos import ChaosHarness, SLOSpec

        r_off = ChaosHarness(self._scenario(None)).run()
        slo = SLOSpec(accepted_p99_s=1e-9, window_s=1.0)
        # NO manual obs.enable(): a Scenario.slo enables telemetry for
        # the run itself (a watchdog over unpublished counters would
        # score every window a silent zero) and restores it after
        r_on = ChaosHarness(self._scenario(slo)).run()
        assert not obs.enabled()
        # SLO evaluation is a pure observer: digests pinned identical
        assert r_off.trace.digest() == r_on.trace.digest()
        assert r_on.slo is not None
        # the impossible latency target breaches every closed round
        assert r_on.slo["breaches"]
        assert r_on.slo["state"][0]["breached"]
        assert r_on.summary()["slo_breaches"] == len(r_on.slo["breaches"])
        assert "slo_breaches" not in r_off.summary()

    def test_duplicate_tenant_slos_rejected(self):
        reg = _registry()
        with pytest.raises(ValueError, match="duplicate TenantSLO"):
            SLOWatchdog(
                [
                    TenantSLO(tenant="m0", accepted_p99_s=1.0),
                    TenantSLO(tenant="m0", failed_round_rate=0.1),
                ],
                registry=reg,
            )

    def test_slo_spec_json_roundtrip(self):
        from byzpy_tpu.chaos import SLOSpec, Scenario

        s = self._scenario(SLOSpec(failed_round_rate=0.1, window_s=2.0))
        assert Scenario.from_dict(json.loads(s.to_json())) == s


class TestMultiwindowBurn:
    """ISSUE-14 satellite: the SRE-workbook multiwindow convention
    — short/long-window burn pairs with page (~14×) / ticket (~1–6×)
    presets; breach requires BOTH windows over threshold; the
    single-window path stays byte-identical when no policy is set."""

    def test_presets_carry_the_convention(self):
        from byzpy_tpu.observability.slo import BurnRatePolicy

        page = BurnRatePolicy.page()
        assert page.severity == "page"
        assert page.burn_threshold == pytest.approx(14.0)
        assert page.short_window_s < page.long_window_s
        ticket = BurnRatePolicy.ticket()
        assert ticket.severity == "ticket"
        assert 1.0 <= ticket.burn_threshold <= 6.0
        assert ticket.long_window_s > page.long_window_s
        with pytest.raises(ValueError):
            BurnRatePolicy(short_window_s=10.0, long_window_s=5.0,
                           burn_threshold=14.0)
        with pytest.raises(ValueError):
            BurnRatePolicy(short_window_s=1.0, long_window_s=5.0,
                           burn_threshold=0.0)

    def _watchdog(self, reg, clock, *, threshold=2.0):
        from byzpy_tpu.observability.slo import BurnRatePolicy

        return SLOWatchdog(
            [
                TenantSLO(
                    tenant="m0",
                    failed_round_rate=0.1,
                    burn=BurnRatePolicy(
                        short_window_s=5.0,
                        long_window_s=50.0,
                        burn_threshold=threshold,
                    ),
                )
            ],
            registry=reg,
            clock=lambda: clock[0],
        )

    def test_sustained_burn_breaches_both_windows(self):
        reg = _registry()
        clock = [0.0]
        w = self._watchdog(reg, clock)
        failed = reg.counter(
            "byzpy_serving_failed_rounds_total", labels={"tenant": "m0"}
        )
        rounds = reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": "m0"}
        )
        # sustained 50% failure rate (5x the 10% budget > 2x threshold)
        for t in (1.0, 2.0, 3.0, 4.0):
            failed.inc(2)
            rounds.inc(2)
            clock[0] = t
            (row,) = w.evaluate()
        assert row["burn"] == pytest.approx(5.0)
        assert row["short_burn"] == pytest.approx(5.0)
        assert row["severity"] == "page"
        assert row["breached"]
        # both series on the scrape: long on byzpy_slo_burn_rate, short
        # on byzpy_slo_short_burn_rate
        text = reg.prometheus_text()
        assert "byzpy_slo_burn_rate" in text
        assert "byzpy_slo_short_burn_rate" in text

    def test_ended_spike_does_not_page(self):
        """A burst that already stopped: the LONG window still carries
        the badness but the SHORT window is clean — no page (the
        whole point of the multiwindow AND)."""
        reg = _registry()
        clock = [0.0]
        w = self._watchdog(reg, clock)
        failed = reg.counter(
            "byzpy_serving_failed_rounds_total", labels={"tenant": "m0"}
        )
        rounds = reg.counter(
            "byzpy_serving_rounds_total", labels={"tenant": "m0"}
        )
        failed.inc(8)
        rounds.inc(8)
        clock[0] = 1.0
        (row,) = w.evaluate()
        assert row["breached"]  # burst in both windows: page
        # clean traffic for longer than the short window
        for t in (3.0, 6.0, 9.0, 12.0):
            rounds.inc(3)
            clock[0] = t
            (row,) = w.evaluate()
        # long window still remembers (burn > threshold) but the short
        # window is clean -> breach clears
        assert row["burn"] > 2.0
        assert row["short_burn"] == 0.0
        assert not row["breached"]

    def test_single_window_rows_unchanged_shape(self):
        """No policy attached: rows keep the single-window shape (no
        severity/short keys) — existing configs unchanged."""
        reg = _registry()
        w = SLOWatchdog(
            [TenantSLO(tenant="m0", failed_round_rate=0.1)],
            registry=reg,
        )
        (row,) = w.evaluate()
        assert "severity" not in row and "short_burn" not in row
