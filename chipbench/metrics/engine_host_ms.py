"""engine_host_ms (ms): mean host time per engine step outside
``adapter.step`` (admission, prefill assembly, retire with sampling, the
client driver's resubmissions), over every step of the window."""


def read(run):
    steps = run.steps
    return 1e3 * sum(s.end - s.start - s.decode_s for s in steps) / len(steps)
