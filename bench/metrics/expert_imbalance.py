"""How unevenly the router loads the held experts: over the window steps,
the mean of the largest held expert's rows over the mean held expert's
(the step counters ``moe_rows_max`` and ``moe_rows``, each summed over the
MoE layers).  1.0 is perfect balance; the largest expert sets the grouped
matmul's longest group.  A step whose held experts got no rows has no
ratio and is left out.  Reports nothing where the steps carry no such
counters, or none of them a row."""


def read(run):
    held = run.cell.model.dims(run.cell.conf).get("held")
    counters = [getattr(m, "counters", {}) for m in run.steps]
    if not held or not counters or not all("moe_rows" in c for c in counters):
        return None
    ratios = [c["moe_rows_max"] / (c["moe_rows"] / held) for c in counters if c["moe_rows"]]
    return sum(ratios) / len(ratios) if ratios else None
