"""decode_launches (count): programs launched (``PjitFunction(...)``
host events, each counted once) and host->device puts (``DevicePut``
events) that start inside the program's ``repro.adapter.step`` span,
per traced engine step."""
import spans


def read(run):
    return spans.launches(run)
