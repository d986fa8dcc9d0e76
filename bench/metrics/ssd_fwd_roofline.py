"""The SSD forward kernel's share of its roofline: over its events in the
trace, the least time the chip needs for each call (the larger of its
operations over peak FLOP/s and its bytes over peak bytes/s, from
``ssd_kernel_cost`` of the model file) over the time the calls took.
Reports nothing where the trace holds no such kernel."""
import sys

from bench.peaks import peak

#: The kernel's HLO instruction in the trace is named after the program's
#: ``ssd_scan`` (kernels/ssd.py), which gives its ``pallas_call`` no name.
KERNEL = "ssd_scan"


def read(run):
    cost = getattr(run.cell.model, "ssd_kernel_cost", None)
    if cost is None:
        return None
    calls, seconds = run.trace.op_time(lambda name: name.startswith(KERNEL))
    if calls == 0:
        return None
    flops, nbytes = cost(run.cell.conf, run.cell.batch, run.cell.seq_len)
    p = peak(run.device["kind"])
    compute, memory = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    bound = "bytes" if memory >= compute else "operations"
    print(f"ssd_fwd_roofline: {calls} calls, {seconds} s, bound by {bound}", file=sys.stderr)
    return 100.0 * calls * max(compute, memory) / seconds
