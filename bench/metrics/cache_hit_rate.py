"""Share of the window's samples served by the node cache (the loader's
hit and miss counts).  A cell without a cache reports nothing."""


def read(run):
    if run.cell.traffic["cache_items"] is None:
        return None
    hits = sum(m.hits for m in run.steps)
    return 100.0 * hits / sum(m.hits + m.misses for m in run.steps)
