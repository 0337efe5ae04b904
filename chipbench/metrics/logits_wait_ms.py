"""logits_wait_ms (ms): host time in the program's
``repro.adapter.logits`` span (the decode call's wait for the device and
the copy of the logits to the host), per traced engine step."""
import spans


def read(run):
    return spans.mean_ms(run, "repro.adapter.logits")
