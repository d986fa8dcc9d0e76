"""Operation and byte counts against hand counts at smoke sizes."""
import copy

from bench import harness


def _conf(config: str):
    conf = copy.deepcopy(harness.load_json(harness.BENCH / "configs" / f"{config}.json"))
    model = harness.load_module(harness.BENCH / "models" / f"{conf['model_type']}.py")
    conf.update(model.SMOKE)
    return conf, model


def test_mamba2_counts():
    conf, model = _conf("mamba2-130m")
    # d 64, inner 128, 8 heads of 16, one group, state 16, conv 4, chunk 16,
    # 2 layers, vocabulary 500 padded to 512 rows.
    # SSD per token: C B^T 2*16*16 = 512 for the group; per head intra
    # 2*16*16 = 512, inter 2*16*16 = 512, state 2*16*16 = 512 -> 8 * 1536.
    assert model.ssd_flops_per_token(model.dims(conf)) == 512 + 8 * 1536
    # projections 64*(256+32+8) + 128*64 = 27136, conv 4*160 = 640;
    # forward 2*(2*27136 + 2*640 + 12800) + head 2*64*512 = 202240.
    assert model.train_flops_per_token(conf, 64) == 3 * 202240
    # batch 4 x seq 64 = 256 tokens: x and y 2*8*16 bf16, dt 8 f32, B and C
    # 2*16 bf16 per token (608 B); final state 4*8*16*16 f32 = 32768 B.
    assert model.ssd_kernel_cost(conf, 4, 64) == (256 * 12800, 256 * 608 + 32768)


def test_mamba2_matmul_count_matches_parameters():
    """Twice the projection and head parameters per token, as the program
    counts them, is the forward count less the scan and the conv.  (The
    program's count leaves out the conv bias, so it is not subtracted.)"""
    conf, model = _conf("mamba2-130m")
    cfg = model.arch_config(conf)
    D = model.dims(conf)
    L, H, di, d = D["L"], D["H"], D["di"], D["d"]
    non_matmul = (
        L * (D["K"] * (di + 2 * D["G"] * D["N"]) + 3 * H + di + d) + d
    )
    matmul_params = cfg.param_count() - non_matmul
    scan_and_conv = L * (model.ssd_flops_per_token(D) + 2 * D["K"] * (di + 2 * D["G"] * D["N"]))
    assert model.train_flops_per_token(conf, 64) / 3 == 2 * matmul_params + scan_and_conv
