"""plan_phase1_s: the program's own time in phase 1 of its plans during
set-up, the sum of its ``plan.build_s`` histogram (one observation per
``flexagon_plan``: the gate/up plan and the down plan; the benchmark's
process plans nothing else)."""

from repro import obs


def read(rec):
    h = obs.get_registry().get("plan.build_s")
    return h.sum if h is not None and h.count else None
