"""Observability wired through the real fabrics.

* a serving round closed through the production path emits the full
  lifecycle span catalog and publishes the tenant's registry metrics;
* the TCP ingress answers an HTTP GET with a Prometheus scrape of the
  registry (and wire frames still work on the same port);
* the actor-mode ParameterServer emits round/gather/aggregate/broadcast
  spans and round metrics;
* chaos digests are BIT-IDENTICAL with telemetry on or off (the
  regression pin for the EventTrace mirror);
* the overhead budget: the disabled path costs one flag check (no
  allocation), and enabled telemetry projects to <5% of a serving
  round's latency.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from byzpy_tpu import observability as obs
from byzpy_tpu.observability import metrics as obs_metrics
from byzpy_tpu.observability import tracing as obs_tracing

#: Every stage the ISSUE's acceptance criterion names for one serving
#: round recorded end-to-end (ingress decode is TCP-only, asserted in
#: the socket test below).
LIFECYCLE_SPANS = {
    "serving.admission",
    "serving.round",
    "serving.cohort_close",
    "serving.bucket_pad",
    "serving.fold",
    "serving.device_step",
    "serving.broadcast",
}


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    obs.disable()
    obs_tracing.tracer().clear()
    obs_tracing.adopt_context(None)
    yield
    obs.disable()
    obs_tracing.tracer().clear()
    obs_tracing.adopt_context(None)


def _frontend(dim=32, name="m0", min_bucket=2):
    from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
    from byzpy_tpu.serving import ServingFrontend, TenantConfig

    return ServingFrontend(
        [
            TenantConfig(
                name=name,
                aggregator=CoordinateWiseTrimmedMean(f=1),
                dim=dim,
                window_s=0.01,
                cohort_cap=16,
                min_bucket=min_bucket,
            )
        ]
    )


def _submit_round(fe, dim=32, m=4, tenant="m0", round_id=None):
    rid = fe.round_of(tenant) if round_id is None else round_id
    rng = np.random.default_rng(0)
    for i in range(m):
        req = {
            "kind": "submit",
            "tenant": tenant,
            "client": f"c{i}",
            "round": rid,
            "gradient": rng.normal(size=dim).astype(np.float32),
        }
        reply = fe.handle_request(req)
        assert reply["accepted"], reply
    closed = fe.close_round_nowait(tenant)
    assert closed is not None
    return closed


class TestServingLifecycle:
    def test_round_emits_every_lifecycle_span(self):
        obs.enable()
        fe = _frontend()
        _submit_round(fe)
        names = {ev["name"] for ev in obs_tracing.tracer().events()}
        assert LIFECYCLE_SPANS <= names, LIFECYCLE_SPANS - names
        # round span carries tenant/round/m and rides the tenant track
        rounds = [
            ev
            for ev in obs_tracing.tracer().events()
            if ev["name"] == "serving.round"
        ]
        assert rounds[0]["args"]["tenant"] == "m0"
        assert rounds[0]["args"]["round"] == 0
        assert rounds[0]["args"]["m"] == 4
        # the executor-thread stages are attributed to the tenant too
        for stage in ("serving.fold", "serving.device_step",
                      "serving.bucket_pad"):
            (ev,) = [
                e for e in obs_tracing.tracer().events()
                if e["name"] == stage
            ]
            assert ev["args"]["tenant"] == "m0", stage

    def test_round_publishes_registry_metrics(self):
        obs.enable()
        reg = obs_metrics.registry()
        acc = reg.counter(
            "byzpy_serving_submissions_total",
            labels={"tenant": "m1", "outcome": "accepted"},
        )
        fe = _frontend(name="m1")
        before = acc.value
        _submit_round(fe, tenant="m1")
        assert acc.value == before + 4
        rounds = reg.counter("byzpy_serving_rounds_total", labels={"tenant": "m1"})
        assert rounds.value >= 1
        lat = reg.histogram(
            "byzpy_serving_round_latency_seconds", labels={"tenant": "m1"}
        )
        assert lat.count >= 1
        cohort = reg.histogram(
            "byzpy_serving_cohort_size", labels={"tenant": "m1"},
            buckets=obs_metrics.SIZE_BUCKETS,
        )
        assert cohort.count >= 1
        dim = reg.gauge("byzpy_serving_tenant_dim", labels={"tenant": "m1"})
        assert dim.value == 32

    def test_disabled_round_records_nothing(self):
        fe = _frontend(name="m2")
        _submit_round(fe, tenant="m2")
        assert obs_tracing.tracer().events() == []

    def test_stats_dict_unchanged_by_telemetry(self):
        # the back-compat stats() shim must not depend on the flag
        fe_off = _frontend(name="m3")
        _submit_round(fe_off, tenant="m3")
        off = fe_off.stats()["m3"]
        obs.enable()
        fe_on = _frontend(name="m4")
        _submit_round(fe_on, tenant="m4")
        on = fe_on.stats()["m4"]
        for key in ("rounds", "round_id", "mean_cohort", "failed_rounds",
                    "outstanding", "queue_depth", "min_cohort"):
            assert off[key] == on[key], key


@pytest.mark.slow
class TestPrometheusIngress:
    def test_http_scrape_and_wire_frames_share_the_port(self):
        async def run():
            from byzpy_tpu.serving.frontend import ServingClient

            obs.enable()
            fe = _frontend(name="m5", dim=64)
            host, port = await fe.serve()
            # 1) wire submissions over TCP (counts ingress bytes/frames)
            client = ServingClient()
            await client.connect(host, port)
            for i in range(4):
                ack = await client.submit(
                    "m5", f"c{i}", 0, np.ones(64, np.float32)
                )
                assert ack["accepted"], ack
            await client.close()
            fe.close_round_nowait("m5")
            # 2) HTTP scrape on the SAME port
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read(-1)
            writer.close()
            await fe.close()
            return raw, fe

        raw, fe = asyncio.run(run())
        head, _, body = raw.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.0 200 OK")
        assert b"text/plain; version=0.0.4" in head
        text = body.decode()
        assert "# TYPE byzpy_serving_submissions_total counter" in text
        assert 'byzpy_serving_rounds_total{tenant="m5"}' in text
        assert "byzpy_serving_round_latency_seconds_bucket" in text
        assert 'byzpy_wire_info{precision="off",signed="0"} 1' in text
        # ingress accounting followed the submit frames
        reg = obs_metrics.registry()
        frames = reg.counter(
            "byzpy_serving_submit_frames_total", labels={"tenant": "m5"}
        )
        nbytes = reg.counter(
            "byzpy_serving_ingress_bytes_total", labels={"tenant": "m5"}
        )
        assert frames.value >= 4
        assert nbytes.value == fe._tenants["m5"].ingress_bytes
        # the TCP path adds the ingress decode span to the lifecycle
        names = {ev["name"] for ev in obs_tracing.tracer().events()}
        assert "serving.ingress.decode" in names
        # ...and the cross-process linkage holds on the REAL socket
        # path: every admission span is the client submit span's child
        # (the decode span's exit must not wipe the adopted context)
        events = obs_tracing.tracer().events()
        submit_ids = {
            ev["args"]["span"]
            for ev in events
            if ev["name"] == "serving.client.submit"
        }
        admissions = [
            ev for ev in events if ev["name"] == "serving.admission"
        ]
        assert len(submit_ids) >= 4 and len(admissions) >= 4
        for ev in admissions:
            assert ev["args"].get("parent") in submit_ids, ev["args"]

    def test_scrape_does_not_count_as_bad_frame(self):
        async def run():
            fe = _frontend(name="m6")
            host, port = await fe.serve()
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET / HTTP/1.0\r\n\r\n")
            await writer.drain()
            raw = await reader.read(-1)
            writer.close()
            await fe.close()
            return raw, fe.bad_frames

        raw, bad = asyncio.run(run())
        assert raw.startswith(b"HTTP/1.0 200 OK")
        assert bad == 0


class TestActorPS:
    def test_round_spans_and_metrics(self):
        from byzpy_tpu.aggregators import CoordinateWiseMedian
        from byzpy_tpu.engine.parameter_server import ParameterServer

        class _Node:
            def __init__(self, v):
                self.v = np.full(8, v, np.float32)

            def honest_gradient_for_next_batch(self):
                return self.v

            def apply_server_gradient(self, g):
                pass

        async def run():
            obs.enable()
            ps = ParameterServer(
                honest_nodes=[_Node(1.0), _Node(2.0), _Node(3.0)],
                aggregator=CoordinateWiseMedian(),
            )
            return await ps.round()

        agg = asyncio.run(run())
        np.testing.assert_allclose(np.asarray(agg), np.full(8, 2.0))
        names = {ev["name"] for ev in obs_tracing.tracer().events()}
        assert {"ps.round", "ps.gather", "ps.aggregate", "ps.broadcast"} <= names
        reg = obs_metrics.registry()
        assert (
            reg.counter("byzpy_ps_rounds_total", labels={"mode": "serial"}).value
            >= 1
        )
        assert reg.histogram("byzpy_ps_round_seconds").count >= 1


class TestWireCounters:
    def test_encode_decode_count_frames_and_bytes(self):
        from byzpy_tpu.engine.actor import wire

        obs.enable()
        reg = obs_metrics.registry()
        tx_f = reg.counter("byzpy_wire_frames_total", labels={"direction": "tx"})
        tx_b = reg.counter("byzpy_wire_bytes_total", labels={"direction": "tx"})
        rx_f = reg.counter("byzpy_wire_frames_total", labels={"direction": "rx"})
        f0, b0, r0 = tx_f.value, tx_b.value, rx_f.value
        frame = wire.encode({"kind": "submit", "gradient": np.ones(128)})
        wire.decode(frame[4:])
        assert tx_f.value == f0 + 1
        assert tx_b.value == b0 + len(frame)
        assert rx_f.value == r0 + 1

    def test_disabled_counts_nothing(self):
        from byzpy_tpu.engine.actor import wire

        reg = obs_metrics.registry()
        tx = reg.counter("byzpy_wire_frames_total", labels={"direction": "tx"})
        before = tx.value
        wire.encode({"x": 1})
        assert tx.value == before


class TestChaosTelemetry:
    def _scenario(self):
        from byzpy_tpu.chaos import ArrivalModel, AttackSpec, Scenario

        return Scenario(
            name="obs",
            seed=77,
            n_clients=6,
            n_byzantine=1,
            dim=8,
            rounds=4,
            aggregator="trimmed_mean",
            aggregator_params={"f": 1},
            attack=AttackSpec(name="sign_flip"),
            arrivals=ArrivalModel(kind="bernoulli", p=0.9),
        )

    def test_digest_identical_with_telemetry_on(self):
        from byzpy_tpu.chaos import ChaosHarness

        r_off = ChaosHarness(self._scenario()).run()
        obs.enable()
        r_on = ChaosHarness(self._scenario()).run()
        # the regression pin: mirroring events onto the tracer must not
        # perturb the replay/determinism contract
        assert r_off.trace.digest() == r_on.trace.digest()
        assert len(r_off.trace) == len(r_on.trace)
        chaos_events = [
            ev
            for ev in obs_tracing.tracer().events()
            if ev["name"].startswith("chaos.")
        ]
        assert len(chaos_events) == len(r_on.trace)
        kinds = {ev["name"] for ev in chaos_events}
        assert "chaos.round_close" in kinds and "chaos.arrive" in kinds

    def test_event_trace_chrome_export(self, tmp_path):
        from byzpy_tpu.chaos import ChaosHarness

        report = ChaosHarness(self._scenario()).run()
        path = str(tmp_path / "chaos.json")
        n = report.trace.to_chrome_trace(path)
        with open(path) as fh:
            doc = json.load(fh)
        assert n == len(doc["traceEvents"]) > 0
        rounds = [
            e for e in doc["traceEvents"] if e["name"] == "chaos.round"
        ]
        # every round_close (closed OR held) becomes a complete span
        assert len(rounds) == len(report.trace.of_kind("round_close"))
        # virtual time: round r spans start at r * window_s seconds (µs)
        s = self._scenario()
        for ev in rounds:
            r = ev["args"]["round"]
            assert ev["ts"] <= r * s.window_s * 1e6 + s.window_s * 1e6


class TestShardedTier:
    def _coordinator(self, tenant="shardobs", n_shards=2, dim=16):
        from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
        from byzpy_tpu.serving import ShardedCoordinator, TenantConfig

        return ShardedCoordinator(
            [
                TenantConfig(
                    name=tenant,
                    aggregator=CoordinateWiseTrimmedMean(f=1),
                    dim=dim,
                    window_s=0.01,
                    cohort_cap=16,
                )
            ],
            n_shards,
            quorum=1,
        )

    def _run_rounds(self, co, tenant="shardobs", dim=16, rounds=2):
        rng = np.random.default_rng(3)
        vecs = []
        for r in range(rounds):
            for i in range(8):
                ok, reason = co.submit(
                    tenant, f"c{i:02d}", r, rng.normal(size=dim).astype(np.float32),
                    seq=r,
                )
                assert ok, reason
            closed = co.close_round_nowait(tenant)
            assert closed is not None
            vecs.append(np.asarray(closed[2]))
        return vecs

    def test_sharded_round_stitches_into_one_tree(self):
        from byzpy_tpu.observability import critical_path as cp

        obs.enable()
        co = self._coordinator()
        self._run_rounds(co, rounds=2)
        events = obs_tracing.tracer().events()
        rounds = cp.round_roots(cp.build_forest(events))
        assert [r.name for r in rounds] == [
            "serving.sharded_round", "serving.sharded_round",
        ]
        tree = rounds[0]
        child_names = {c.name for c in tree.children}
        assert "serving.shard_close" in child_names
        assert "serving.fold_merge" in child_names
        # shard_close spans carry the shard dim; the merge span links
        # every partial's carried context
        shard_dims = {
            c.shard for c in tree.children
            if c.name == "serving.shard_close"
        }
        assert shard_dims == {0, 1}
        (merge,) = [
            c for c in tree.children if c.name == "serving.fold_merge"
        ]
        assert len(merge.args["links"]) == 2
        assert {"serving.device_step"} <= {
            c.name for c in merge.children
        }
        # blame partitions the round makespan
        summary = cp.summarize(events)
        assert summary["max_blame_residual"] < 1e-6
        stages = {r["stage"] for r in summary["stages"]}
        assert "serving.fold_merge" in stages

    def test_partial_fold_wire_carries_context_and_links_remote_root(self):
        from byzpy_tpu.serving.sharded import (
            decode_partial_fold, encode_partial_fold,
        )

        obs.enable()
        co = self._coordinator(tenant="shardwire")
        rng = np.random.default_rng(4)
        for i in range(8):
            co.submit(
                "shardwire", f"c{i:02d}", 0,
                rng.normal(size=16).astype(np.float32), seq=0,
            )
        partials = [
            s.close_partial("shardwire") for s in co.shards
        ]
        partials = [p for p in partials if p is not None]
        assert partials and all(p.trace_ctx is not None for p in partials)
        # the wire round-trip preserves the context (and the frame dict
        # exposes no telemetry key to the consumer)
        p = partials[0]
        q = decode_partial_fold(encode_partial_fold(p)[4:])
        assert q.trace_ctx == p.trace_ctx
        res = co.merge_partials("shardwire", partials)
        assert res is not None
        merges = [
            ev for ev in obs_tracing.tracer().events()
            if ev["name"] == "serving.fold_merge"
        ]
        assert merges[-1]["args"]["links"] == [
            f"{p.trace_ctx[0]}:{p.trace_ctx[1]}" for p in partials
        ]

    def test_aggregates_bit_identical_propagation_on_off(self):
        # the acceptance pin: trace-context propagation must never
        # perturb round arithmetic
        co_off = self._coordinator(tenant="paroff")
        off = self._run_rounds(co_off, tenant="paroff", rounds=2)
        obs.enable()
        co_on = self._coordinator(tenant="paron")
        on = self._run_rounds(co_on, tenant="paron", rounds=2)
        for a, b in zip(off, on, strict=True):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.slow
    def test_scrape_serves_shard_tenant_and_slo_families_together(self):
        from byzpy_tpu.observability.slo import SLOWatchdog, TenantSLO

        async def run():
            obs.enable()
            co = self._coordinator(tenant="shardslo")
            self._run_rounds(co, tenant="shardslo", rounds=2)
            watchdog = SLOWatchdog(
                [
                    TenantSLO(
                        tenant="shardslo", accepted_p99_s=5.0,
                        failed_round_rate=0.5,
                    )
                ]
            )
            watchdog.evaluate()
            # the ROOT ingress: shard 0's inner frontend's TCP port
            # (the registry is process-wide — one scrape sees the
            # whole tier)
            host, port = await co.shards[0].frontend.serve()
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read(-1)
            writer.close()
            await co.shards[0].frontend.close()
            watchdog.close()
            return raw

        raw = asyncio.run(run())
        head, _, body = raw.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.0 200 OK")
        text = body.decode()
        # the three families the sharded tier's operators dashboard on,
        # in ONE scrape: per-shard, per-tenant, and SLO
        for needle in (
            'byzpy_shard_rounds_total{tenant="shardslo"}',
            'byzpy_shard_accepted_total{shard="0",tenant="shardslo"}',
            'byzpy_serving_submissions_total{outcome="accepted",tenant="shardslo"}',
            'byzpy_slo_burn_rate{objective="accepted_p99",tenant="shardslo"}',
            "# TYPE byzpy_slo_breaches_total counter",
        ):
            assert needle in text, f"scrape missing {needle!r}"


class TestOverheadBudget:
    def test_disabled_span_is_flag_check_cheap(self):
        # the disabled front door must be a flag check returning the
        # shared singleton — bound the per-call cost generously so CI
        # noise cannot flake this (measured ~0.1-0.3 µs)
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_tracing.span("hot"):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"disabled span cost {per_call * 1e9:.0f} ns"

    def test_enabled_overhead_projects_under_5pct_of_round_latency(self):
        # deterministic form of the <5% p99 budget: measure the enabled
        # span cost, count the spans a serving round emits, and compare
        # the projected telemetry cost against the measured round time.
        # Best-of-5 trials: the microbench runs inside a loaded test
        # process, and a GC pause mid-trial must not fail the budget —
        # the minimum is the cost the instrumentation actually has.
        obs.enable()
        n = 2_000
        span_cost = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with obs_tracing.span("hot", round=1, m=4):
                    pass
            span_cost = min(span_cost, (time.perf_counter() - t0) / n)
        obs_tracing.tracer().clear()

        # serving-bench-shaped round (dim 1024), not a toy one — the
        # budget is relative, so an artificially tiny round would fail
        # instrumentation that is fine at any realistic cohort
        fe = _frontend(name="m7", dim=1024)
        # warm the jit cache so the measured rounds are steady-state
        _submit_round(fe, dim=1024, m=8, tenant="m7")
        spans_per_round = len(obs_tracing.tracer().events())
        assert spans_per_round >= len(LIFECYCLE_SPANS)
        durations = []
        for _ in range(20):
            t0 = time.perf_counter()
            _submit_round(fe, dim=1024, m=8, tenant="m7")
            durations.append(time.perf_counter() - t0)
        durations.sort()
        p99 = obs_metrics.percentile_of_sorted(durations, 99)
        projected = span_cost * spans_per_round
        assert projected < 0.05 * p99, (
            f"telemetry projects {projected * 1e6:.1f} µs/round against a "
            f"{p99 * 1e6:.1f} µs p99 round"
        )

    def test_enabled_vs_disabled_round_latency_budget(self):
        # end-to-end guard with generous slack (CI boxes are noisy):
        # enabled must stay within 1.5x + 2 ms of the disabled median
        def measure(tenant):
            fe = _frontend(name=tenant, dim=256)
            _submit_round(fe, dim=256, m=4, tenant=tenant)  # warm compile
            durs = []
            for _ in range(15):
                t0 = time.perf_counter()
                _submit_round(fe, dim=256, m=4, tenant=tenant)
                durs.append(time.perf_counter() - t0)
            return sorted(durs)[len(durs) // 2]

        obs.disable()
        base = measure("m8")
        obs.enable()
        on = measure("m9")
        assert on <= base * 1.5 + 0.002, (base, on)
