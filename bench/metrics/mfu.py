"""The whole step's share of the chip's bf16 peak: model operations per
token (forward and backward, recompute not counted) times the window's
tokens per second, over the peak of the chips used."""
from bench.peaks import peak


def read(run):
    cell = run.cell
    flops = cell.model.train_flops_per_token(cell.conf, cell.seq_len)
    tokens = len(run.steps) * cell.batch * cell.seq_len
    chips = cell.chips * peak(run.device["kind"])["bf16_flops"]
    return 100.0 * flops * tokens / run.window_s / chips
