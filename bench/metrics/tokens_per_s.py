"""Training tokens of every window step over the window's wall time."""


def read(run):
    return len(run.steps) * run.cell.batch * run.cell.seq_len / run.window_s
