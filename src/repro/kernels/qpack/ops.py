"""Public pack/unpack entry points for the comm codecs.

``quantize_blocks`` / ``dequantize_blocks`` flatten any batch of flat
streams to (R, N), pad N up to the block multiple, and run either the
Pallas kernels (the device path; interpret-mode execution validates the
kernel bodies off-TPU) or the pure-jnp ref oracle.  Like
``kernels/fedavg``, the two paths are interchangeable — ``use_kernel=None``
picks the kernel on a real TPU backend and the vectorized ref elsewhere, so
the jitted round on CPU never pays interpret-mode overhead.

Wire format (what ``repro.comm`` bills): ``ceil(N * bits / 8)`` payload
bytes + one f16 scale per ``block`` — padding lanes are a tiling artifact
and are trimmed before anything ships.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.qpack import kernel, ref


def _use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_rows(x: jax.Array, block: int):
    """(..., N) -> (R, Np) padded to the block multiple, + restore info."""
    lead = x.shape[:-1]
    N = x.shape[-1]
    rows = x.reshape(-1, N)
    pad = (-N) % block
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return rows, lead, N


def quantize_blocks(x: jax.Array, *, bits: int = 8, block: int = 128,
                    use_kernel: bool | None = None):
    """x: (..., N) -> (payload, scales).

    payload: int8 codes (..., Np) for bits=8, packed uint8 nibbles
    (..., Np // 2) for bits=4 (Np = N padded to ``block``); scales: f16
    (..., Np // block)."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if block < 2 or block % 2:
        raise ValueError(f"block must be even and >= 2, got {block}")
    kern = _use_kernel_default() if use_kernel is None else use_kernel
    qmax = 2 ** (bits - 1) - 1
    rows, lead, _ = _to_rows(x, block)
    if kern:
        q, s = kernel.quant_flat(rows, qmax=qmax, block=block,
                                 interpret=_interpret())
        if bits == 4:
            q = kernel.pack4_flat(q, interpret=_interpret())
    else:
        q, s = ref.quant_blocks_ref(rows, qmax=qmax, block=block)
        if bits == 4:
            q = ref.pack4_ref(q)
    return q.reshape(lead + q.shape[1:]), s.reshape(lead + s.shape[1:])


def roundtrip_blocks(x: jax.Array, *, bits: int = 8, block: int = 128,
                     use_kernel: bool | None = None) -> jax.Array:
    """Fused quantize→dequantize: the lossy wire image without the nibble
    pack/unpack (pack4∘unpack4 is a bit-exact identity — wasted work on
    the sync hot path, where only the values matter, not the wire bytes)."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    kern = _use_kernel_default() if use_kernel is None else use_kernel
    qmax = 2 ** (bits - 1) - 1
    rows, lead, n = _to_rows(x, block)
    if kern:
        q, s = kernel.quant_flat(rows, qmax=qmax, block=block,
                                 interpret=_interpret())
        out = kernel.dequant_flat(q, s, block=block, interpret=_interpret())
    else:
        q, s = ref.quant_blocks_ref(rows, qmax=qmax, block=block)
        out = ref.dequant_blocks_ref(q, s, block=block)
    return out[:, :n].reshape(lead + (n,))


def dequantize_blocks(payload: jax.Array, scales: jax.Array, *, n: int,
                      bits: int = 8, block: int = 128,
                      use_kernel: bool | None = None) -> jax.Array:
    """Inverse of :func:`quantize_blocks`; returns f32 (..., n) with the
    padding lanes trimmed."""
    kern = _use_kernel_default() if use_kernel is None else use_kernel
    lead = payload.shape[:-1]
    p = payload.reshape((-1,) + payload.shape[-1:])
    s = scales.reshape((-1,) + scales.shape[-1:])
    if kern:
        q = kernel.unpack4_flat(p, interpret=_interpret()) if bits == 4 else p
        out = kernel.dequant_flat(q, s, block=block, interpret=_interpret())
    else:
        q = ref.unpack4_ref(p) if bits == 4 else p
        out = ref.dequant_blocks_ref(q, s, block=block)
    return out[:, :n].reshape(lead + (n,))
