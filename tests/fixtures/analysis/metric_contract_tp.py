"""byzlint fixture: METRIC-CONTRACT true positives (never imported).

Instruments drifting from the observability catalog: an uncatalogued
metric name, a catalogued name registered under the wrong type, and a
span label the span catalog has never heard of.
"""

from byzpy_tpu.observability import tracing


def register(reg):
    # finding: not in byzpy_tpu/observability/catalog.py
    bogus = reg.counter("byzpy_bogus_total", help="made-up counter")
    # finding: catalogued as a counter, registered as a gauge
    drift = reg.gauge("byzpy_serving_rounds_total", help="wrong type")
    return bogus, drift


def run_phase(payload):
    # finding: span label missing from the span catalog
    with tracing.span("serving.bogus_phase", tenant="t0"):
        return payload
