"""The fused qsync kernel's share of its roofline: the bytes its calls
must move (``harness.flops.qsync_call_bytes``, one call per parameter
leaf) at the chip's HBM rate, over the device seconds of its calls in the
traced rounds.  The kernel is bound by memory: its operations are a few
per element."""
from harness.flops import qsync_call_bytes
from harness.peaks import peak

KERNELS = ("qsync_flat",)


def read(rec):
    t = rec["trace"]
    codec = rec["traffic"]["sync"].get("codec")
    if t is None or codec is None:
        return None
    busy = t["kernel_s"].get("qsync_flat", 0.0)
    if busy <= 0.0:
        return None
    per_round = sum(qsync_call_bytes(n, rec["traffic"]["agents"],
                                     codec["block"])
                    for n in rec["leaf_sizes"])
    least = (per_round * rec["traced_rounds"]
             / peak(rec["device_kind"])["hbm_bytes_per_s"])
    return least / busy * 100.0
