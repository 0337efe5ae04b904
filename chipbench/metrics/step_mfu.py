"""step_mfu (%): model FLOPs of the traced steps (every row's matmuls,
the tied output head and attention over the held positions;
``work/model_step.py``) per second of the traced window, over the
device's bf16 peak."""
import spec


def read(run):
    if run.trace is None:
        return None
    work = spec.work("model_step")
    flops = sum(work.step_flops(run.conf, st) for st in run.traced_steps)
    return 100.0 * flops / run.trace["window_s"] / run.peaks["bf16_flops"]
