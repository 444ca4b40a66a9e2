"""PALP301 positive: span/metric names that dodge the constant table."""


def read(self, tr, key, now):
    sp = tr.start(f"op_{key}", now)           # violation: f-string kind
    tr.event("my_retry", now, node=3)         # violation: ad-hoc literal
    return sp


def record(self, metrics, shard, v):
    kind = "rpc_" + str(shard)
    metrics.counter(kind).inc()               # violation: computed name
    self.tracer.span("demand", 0.0)           # violation: literal kind
    metrics.histogram("lat_" + str(shard)).record(v)   # violation


def profile(self, obs, host_profile, shard):
    with obs.host_profile.span("palp.decide"):   # violation: literal
        host_profile.count(f"copies_{shard}", 1)  # violation: f-string
