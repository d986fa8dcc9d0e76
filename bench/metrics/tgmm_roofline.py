"""The transposed grouped matmul's share of its roofline (``kernels/gmm.py``,
the ``pallas_call`` named ``tgmm``: the experts' weight gradients), read as
``gmm_roofline`` reads ``gmm``, but with every held expert's gradient
written: an expert without rows gets zeros."""
import re

from bench.metrics.gmm_roofline import roofline

#: The kernel's HLO instruction in the trace: ``tgmm`` or ``tgmm.<n>``.
KERNEL = re.compile(r"tgmm(\.\d+)?$")


def read(run):
    return roofline(run, "tgmm_roofline", KERNEL, every_expert=True)
