"""Mean host time per window step outside the loader's data-wait and the
device step seen from the host (``compute_s``): decoding, stacking, the
copy to the device and the loop itself."""


def read(run):
    inside = sum(m.compute_s + m.data_wait_s for m in run.steps)
    return (run.window_s - inside) / len(run.steps) * 1e3
