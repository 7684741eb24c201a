"""Model FLOP/s utilisation of an expert model's train step: the
operations the forward and backward passes need per token
(``rooflines_glm4_moe_lite``: the routed experts by the rows the step's
counters say were routed to the experts held, recomputation not
counted) times tokens per second of the median fenced step, over chips
times the bf16 peak."""
from statistics import median

from benchmark import rooflines_glm4_moe_lite as moe


def picks_per_token(facts: dict):
    """Picks on held experts per token and expert layer, mean over the
    window's steps; None where the step left no counters."""
    rows = [sum(map(sum, c["rows_per_expert"])) / len(c["rows_per_expert"])
            for c in facts.get("counters") or [] if "rows_per_expert" in c]
    if not rows:
        return None
    return sum(rows) / len(rows) / facts["tokens_per_step"]


def read(run):
    f = run.facts
    picks = picks_per_token(f)
    if not f.get("step_s") or picks is None:
        return None
    per_token = moe.train_flops_per_token(f["sizes"], f["seq"], picks)
    rate = f["tokens_per_step"] / median(f["step_s"])
    peak = f["chips"] * f["peaks"]["flops_per_s"][f["dtype"]]
    return 100.0 * per_token * rate / peak
