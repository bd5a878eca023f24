"""The benchmark's arithmetic against counts made by hand at smoke size:
batch 2 x 32 tokens (N = 64), the smoke widths of each configuration."""
import pytest

from harness import flops
from harness.peaks import peak
from harness.program import arch_config, config_dict
from harness.spec import load_cell

B, T, N = 2, 32, 64


def smoke(cell):
    return config_dict(arch_config(load_cell(cell).config, smoke=True))


def test_mamba2_generator_forward():
    c = smoke("mamba2-k5-fedavg")
    # d 128, d_inner 256, 2 SSD heads of 128, state 16, conv 4, chunk 8,
    # 2 layers, vocab 512
    assert (c["d_model"], c["ssm_heads"], c["ssm_state"], c["ssm_chunk"]) == \
        (128, 2, 16, 8)
    proj = 2 * N * 128 * (256 + 256 + 16 + 16 + 2) + 2 * N * 256 * 128
    conv = 2 * N * 4 * (256 + 16 + 16)
    # 4 chunks of 8 per sequence, 36 causal pairs each; per pair C.B over
    # the state and the weighted sum over 2 x 128 head channels; per token
    # the chunk state and its read-out, 2 x 2 x 128 x 16 each
    ssd = B * 4 * 36 * 2 * (16 + 256) + N * 2 * 2 * (2 * 128 * 16)
    head = 2 * N * 128 * 512
    assert proj == 13_139_968 and ssd == 1_205_248
    assert flops.generator_forward(c, B, T) == 2 * (proj + conv + ssd) + head


def test_discriminator_and_step():
    c = smoke("mamba2-k5-fedavg")
    # D: 128 -> 64 input projection, 2 blocks of width 64 (2 heads of 32,
    # SwiGLU 256), head 64 -> 1
    p_in, w, a = flops.discriminator_parts(c, B, T)
    assert p_in == 2 * N * 128 * 64 == 1_048_576
    assert w == 2 * (4 * 2 * N * 64 * 64 + 3 * 2 * N * 64 * 256) + 2 * B * 64
    assert a == 2 * B * (T * T) * 2 * 4 * 32
    g = flops.generator_forward(c, B, T)
    assert flops.step(c, B, T) == 3 * g + 6 * p_in + 8 * w + 9 * a \
        == 262_070_272


def test_round_flops_scale_with_agents_and_steps():
    c = smoke("mamba2-k5-fedavg")
    t = {"agents": 2, "K": 5, "batch": B, "seq_len": T}
    assert flops.round_flops(c, t) == 10 * flops.step(c, B, T)


def test_qsync_bytes():
    # 1000 elements per agent pad to 1024; read x and e of 2 agents, the
    # server residual and 2 weights; write the synced value, 2 residuals
    # and the server's
    assert flops.qsync_call_bytes(1000, 2, 128) == \
        4 * (2 * 1024 + 2 * 1024 + 1024 + 2 + 1024 + 2 * 1024 + 1024)


def test_peaks():
    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary")
