"""Model FLOP/s utilisation: the operations the forward and backward
passes need per token (6 per matmul parameter + causal attention, from
shapes; recomputation not counted) times tokens per second of the
median fenced step, over chips times the bf16 peak."""
from statistics import median

from benchmark import rooflines


def read(run):
    f = run.facts
    if not f.get("step_s"):
        return None
    per_token = rooflines.train_flops_per_token(f["sizes"], f["seq"])
    rate = f["tokens_per_step"] / median(f["step_s"])
    peak = f["chips"] * f["peaks"]["flops_per_s"][f["dtype"]]
    return 100.0 * per_token * rate / peak
