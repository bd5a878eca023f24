"""Model FLOPs of the traced rounds (``harness.flops.round_flops``) over
the traced window times the chips times the chip's peak bf16 rate."""
from harness.flops import round_flops
from harness.peaks import peak


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    done = round_flops(rec["config"], rec["traffic"]) * rec["traced_rounds"]
    return done / (t["window_s"] * rec["chips"]
                   * peak(rec["device_kind"])["bf16_flops"]) * 100.0
