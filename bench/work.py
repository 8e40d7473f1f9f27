"""Work counts and device peaks: the yardstick for every share and rate.

The counts are of the work a pruned SwiGLU FFN layer *needs*, whatever
implements it: effectual FLOPs over the kept weight blocks and the bytes of
the kept weights, the activations in and the output out.  Padding rows, a
re-read weight block or an intermediate written to HBM are the
implementation's cost and are never counted here, so a share of the
roofline or of the peak cannot pass 100% by counting work twice.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Work:
    flops: float        # effectual multiply-adds x 2
    bytes: float        # kept weights + activations in + output out

    def least_time_s(self, peak_flops: float, peak_bytes_per_s: float):
        """(seconds, bound) of the roofline: the larger of the two times."""
        t_flops = self.flops / peak_flops
        t_bytes = self.bytes / peak_bytes_per_s
        return (t_bytes, "memory") if t_bytes >= t_flops \
            else (t_flops, "compute")


def swiglu_ffn_work(*, tokens: int, d_model: int, kept_elems_per_matrix: int,
                    weight_itemsize: int, act_itemsize: int,
                    out_itemsize: int) -> Work:
    """One pruned SwiGLU layer: gate and up (d -> f) and down (f -> d).

    All three matrices share one block mask (down uses its transpose), so
    each keeps ``kept_elems_per_matrix`` weights.
    """
    flops = 3 * 2 * tokens * kept_elems_per_matrix
    nbytes = (3 * kept_elems_per_matrix * weight_itemsize
              + tokens * d_model * act_itemsize
              + tokens * d_model * out_itemsize)
    return Work(float(flops), float(nbytes))


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{Path(path).name}; known: {sorted(table)}")
    return table[device_kind]
