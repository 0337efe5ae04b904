"""packed_matmul_roofline (%): the share of its roofline that packed_matmul reached over
the traced steps (device time from the trace, work from
``work/packed_matmul.py``)."""
import roofline


def read(run):
    return roofline.share(run, "packed_matmul")
