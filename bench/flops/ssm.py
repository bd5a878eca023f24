"""Model FLOPs of the ``ssm`` family's backbone (Mamba2), counted by the
rules in ``harness/flops.py``."""
from harness.flops import causal_pairs


def generator_forward(c: dict, batch: int, T: int) -> float:
    """One forward pass of the backbone over (batch, T) tokens."""
    d, N = c["d_model"], batch * T
    din = c["ssm_expand"] * d
    nh = c["ssm_heads"] or din // 64
    hd, ds, k = din // nh, c["ssm_state"], c["conv_kernel"]
    Q = min(c["ssm_chunk"], T)
    proj = 2 * N * d * (2 * din + 2 * ds + nh) + 2 * N * din * d
    conv = 2 * N * k * (din + 2 * ds)
    # SSD, chunked: causal pairs inside each chunk (C.B scores and
    # their product with x), chunk end states and the inter-chunk
    # read-out of the carried state
    pairs = batch * (T // Q) * causal_pairs(Q)
    ssd = pairs * 2 * (ds + nh * hd) + N * 4 * nh * hd * ds
    layer = proj + conv + ssd
    head = 2 * N * d * c["vocab_size"]
    return c["num_layers"] * layer + head
