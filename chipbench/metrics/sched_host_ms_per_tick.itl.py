"""Host time per engine step that the device spent idle: the harness's
span around ``ContinuousBatchingEngine.step`` minus the device busy time
inside it, averaged over the steps of the traced window (device trace
and harness spans)."""


def read(rec):
    if rec.trace is None or not rec.trace.n_devices:
        return None
    spans = rec.trace.spans_named("step")
    if not spans:
        return None
    host = sum((e.end - e.start) - rec.trace.busy_within(e.start, e.end) for e in spans)
    return 1e3 * host / len(spans)
