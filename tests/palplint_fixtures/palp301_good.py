"""PALP301 negative: registered constants (and out-of-family calls)."""

from repro.core import obs
from repro.core.obs import EVENT_RETRY, METRIC_OPS, SPAN_OP


def read(self, tr, key, now):
    sp = tr.start(SPAN_OP, now)
    tr.event(EVENT_RETRY, now, node=3)
    self.tracer.span(obs.SPAN_RPC, now)
    return sp


def record(self, metrics, v):
    metrics.counter(METRIC_OPS).inc()
    metrics.histogram(obs.METRIC_READ_LATENCY).record(v)


def profile(self, host_profile, n):
    with obs.host_profile.span(obs.SPAN_HOST_DECIDE):
        host_profile.count(obs.METRIC_WALK_H2D_BYTES, n)


def unrelated(self, scheduler, game, now):
    # `.start(...)`/`.event(...)` on non-observability receivers stay
    # legal: the rule keys on tracer/metrics receivers only
    scheduler.start("warmup", now)
    game.event("goal", now)
    scheduler.count("warmup", 1)
