"""engine_idle_share (%): the share of the traced engine steps (first
``repro.engine.step`` start to last end) in which no operation runs on
the device and the host is outside every ``repro.adapter.step`` span:
the device waits on the engine's own host stages."""
import spans


def read(run):
    return spans.engine_idle_share(run)
