"""stream_wait_ms (ms): host time in the program's ``repro.stream.wait``
span (the decode step blocked on a weight stream upload), per traced
engine step."""
import spans


def read(run):
    return spans.mean_ms(run, "repro.stream.wait")
