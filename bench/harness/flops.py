"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs of one adversarial step count each multiply-add as two
operations and leave out what the program computes twice (its remat) or
needlessly (masked halves of causal blocks):

* G, the backbone: the forward pass and both halves of its backward pass
  (input and weight gradients), 3 x its forward.
* D, the feature discriminator, runs three times: on real features and on
  fake ones for D's loss (forward, weight and input gradients; the input
  projection's input gradient is not needed, its input carries none), and
  on the fake features for G's loss (forward and input gradients only).
  So 6 x its input projection, 8 x its other weight products and 9 x its
  attention products.
"""
from __future__ import annotations


def _causal_pairs(T: int) -> int:
    return T * (T + 1) // 2


def generator_forward(c: dict, batch: int, T: int) -> float:
    """One forward pass of the backbone over (batch, T) tokens."""
    if c["family"] != "ssm":
        raise ValueError(f"no FLOP count for family {c['family']!r}")
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
    pairs = batch * (T // Q) * _causal_pairs(Q)
    ssd = pairs * 2 * (ds + nh * hd) + N * 4 * nh * hd * ds
    layer = proj + conv + ssd
    head = 2 * N * d * c["vocab_size"]
    return c["num_layers"] * layer + head


def discriminator_parts(c: dict, batch: int, T: int) -> tuple:
    """(input projection, other weight products, attention products) of
    one forward pass of D over (batch, T) features."""
    d, dd, N = c["d_model"], c["disc_d_model"], batch * T
    heads = c["disc_heads"]
    proj_in = 2 * N * d * dd
    weights = c["disc_layers"] * (4 * 2 * N * dd * dd + 3 * 2 * N * dd * 4 * dd)
    weights += 2 * batch * dd                       # the head
    attn = c["disc_layers"] * batch * T * T * heads * 4 * (dd // heads)
    return proj_in, weights, attn


def step(c: dict, batch: int, T: int) -> float:
    """Model FLOPs of one agent's adversarial step."""
    p_in, w, a = discriminator_parts(c, batch, T)
    return 3 * generator_forward(c, batch, T) + 6 * p_in + 8 * w + 9 * a


def round_flops(c: dict, traffic: dict) -> float:
    t = traffic
    return t["agents"] * t["K"] * step(c, t["batch"], t["seq_len"])


def qsync_call_bytes(n: int, agents: int, block: int) -> int:
    """HBM bytes one ``qsync_flat`` call must move for a leaf of ``n``
    elements per agent, padded to the block: it reads the agents' values
    and residuals, the server residual and the weights, and writes the
    synced value, the new agent residuals and the new server residual,
    all float32."""
    N = -(-n // block) * block
    return 4 * ((3 * agents + 3) * N + agents)
