"""stream_matmul_roofline (%): the share of its roofline that stream_matmul reached over
the traced steps (device time from the trace, work from
``work/stream_matmul.py``)."""
import roofline


def read(run):
    return roofline.share(run, "stream_matmul")
