"""Work counts of the routed experts of a pruned MoE FFN stack: the
yardstick of ``expert_kernel_roofline`` and ``moe_row_fill``.

The work is what the routed experts *need* for the rows routed to them,
whatever implements it: effectual FLOPs of each live row against its
expert's kept blocks, and the bytes of the kept blocks of every expert
that got at least one row, of the rows in and of the rows out.  Padding
rows of a slot, a weight block fetched again for a second slot, and the
intermediates between gate/up and down are the implementation's cost and
are never counted, so the share cannot pass 100% by counting work twice.

The rows come from the program's own per-call counts (live rows and
executed slots per layer and held expert), which the bench system keeps
on the device during the window and reads here after it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .work import Work


def routed_expert_work(rows, *, d_model: int, d_ff: int, kept_fraction: float,
                       weight_itemsize: int, act_itemsize: int) -> Work:
    """One call's routed-expert work, from ``rows`` (..., E): the live rows
    of each held expert (of every MoE layer on the leading axes).

    Each expert keeps ``kept_fraction`` of each of its three matrices' d x f
    weights (gate, up; down the transpose).
    """
    rows = np.asarray(rows, np.int64)
    kept = kept_fraction * d_model * d_ff          # weights per matrix
    flops = 3 * 2 * float(rows.sum()) * kept
    nbytes = (3 * kept * weight_itemsize * float((rows > 0).sum())
              + 2 * float(rows.sum()) * d_model * act_itemsize)
    return Work(flops, nbytes)


@dataclass
class MoEWork:
    """The routed experts' work over the calls a run made.

    ``counts`` is the program's per-call int32 output, one (layers, 2, E)
    array a call in call order (row 0: live rows; row 1: executed slots),
    appended to while the run goes on and read only when asked.
    """

    d_model: int
    d_ff: int
    block: int
    kept_fraction: float
    weight_itemsize: int = 4
    act_itemsize: int = 4
    counts: List = field(default_factory=list)

    @classmethod
    def of_config(cls, cfg: dict, counts: List) -> "MoEWork":
        return cls(cfg["hidden_size"], cfg["moe_intermediate_size"],
                   cfg["block"], 1.0 - cfg["ffn_block_sparsity"],
                   counts=counts)

    def per_call(self, calls: Optional[int] = None) -> np.ndarray:
        """(n, layers, 2, E) counts of the last ``calls`` calls (all if
        None), fetched from the device in one transfer."""
        got = self.counts if calls is None else self.counts[-calls:] \
            if calls else []
        if not got:
            return np.zeros((0, 0, 2, 0), np.int64)
        import jax.numpy as jnp

        return np.asarray(jnp.stack(got)).astype(np.int64)

    def call_work(self, counts) -> Work:
        return routed_expert_work(
            counts[..., 0, :], d_model=self.d_model, d_ff=self.d_ff,
            kept_fraction=self.kept_fraction,
            weight_itemsize=self.weight_itemsize,
            act_itemsize=self.act_itemsize)

    def least_s(self, peak_flops: float, peak_bytes_per_s: float,
                calls: Optional[int] = None) -> float:
        """Least seconds the last ``calls`` calls' routed work needs, each
        call's roofline bound summed."""
        return sum(self.call_work(c).least_time_s(
            peak_flops, peak_bytes_per_s)[0] for c in self.per_call(calls))

    def least_time_s(self, peak_flops: float, peak_bytes_per_s: float):
        """(seconds, bound) of the mean call's roofline over every call."""
        c = self.per_call()
        if not len(c):
            return 0.0, "memory"
        work = self.call_work(c)
        return Work(work.flops / len(c), work.bytes / len(c)).least_time_s(
            peak_flops, peak_bytes_per_s)

    def row_fill(self, calls: Optional[int] = None) -> Optional[float]:
        """Live rows over the rows of the executed slots, in percent."""
        c = self.per_call(calls)
        slots = int(c[..., 1, :].sum()) if len(c) else 0
        if not slots:
            return None
        return 100.0 * float(c[..., 0, :].sum()) / (slots * self.block)
