"""decode_step_ms (ms): mean host time of ``adapter.step`` per engine
step (``packed_decode_step``, ending in the host pull of the logits),
over every step of the window."""


def read(run):
    return 1e3 * sum(s.decode_s for s in run.steps) / len(run.steps)
