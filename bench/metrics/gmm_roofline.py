"""The grouped matmul's share of its roofline (``kernels/gmm.py``, the
``pallas_call`` named ``gmm``: the MoE layers' forward products and input
gradients).  Over the kernel's events in the trace: the least time the chip
needs for each call, the larger of its operations over peak FLOP/s and its
bytes over peak bytes/s (``gmm_kernel_cost`` of the model file), over the
time the calls took.  A call's rows are the real routed rows, not the
padding, and its weights those of the held experts that have rows: the
window steps' ``moe_rows`` and ``moe_experts`` counters over the steps and
the MoE layers, whatever implements the product.  Reports nothing where the
model file has no such cost, the steps no such counters, or the trace no
such kernel."""
import re
import sys

from bench.peaks import peak

#: The kernel's HLO instruction in the trace: ``gmm`` or ``gmm.<n>``.
KERNEL = re.compile(r"gmm(\.\d+)?$")


def roofline(run, name: str, kernel, every_expert: bool) -> float:
    """The reading for the calls whose names ``kernel`` matches, with the
    weights of every held expert (``every_expert``) or of those with rows
    (shared with ``tgmm_roofline``)."""
    cost = getattr(run.cell.model, "gmm_kernel_cost", None)
    counters = [getattr(m, "counters", {}) for m in run.steps]
    if cost is None or not counters or not all("moe_experts" in c for c in counters):
        return None
    calls, seconds = run.trace.op_time(lambda op: kernel.match(op) is not None)
    if calls == 0:
        return None
    dims = run.cell.model.dims(run.cell.conf)
    per_call = len(counters) * dims["moe_layers"]
    rows = sum(c["moe_rows"] for c in counters) / per_call
    experts = dims["held"] if every_expert else sum(c["moe_experts"] for c in counters) / per_call
    flops, nbytes = cost(run.cell.conf, rows, experts)
    p = peak(run.device["kind"])
    compute, memory = flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"]
    bound = "bytes" if memory >= compute else "operations"
    print(f"{name}: {calls} calls, {seconds} s, {rows} rows and {experts} experts a call, "
          f"bound by {bound}", file=sys.stderr)
    return 100.0 * calls * max(compute, memory) / seconds


def read(run):
    return roofline(run, "gmm_roofline", KERNEL, every_expert=False)
