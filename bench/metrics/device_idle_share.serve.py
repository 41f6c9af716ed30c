"""Share of the time with at least one request in flight (the benchmark's
``bench.request`` spans) in which the device runs nothing, in %."""


def read(rec):
    t = rec.trace
    if t is None:
        return None
    from harness.trace import clip, length, merge

    inflight = merge(clip(t.host_spans.get("bench.request", []), *t.window))
    total = length(inflight)
    if total <= 0:
        return None
    return 100.0 * (1.0 - t.busy_within(inflight) / total)
