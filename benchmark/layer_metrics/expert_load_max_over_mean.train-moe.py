"""How unevenly the router loads the experts held: rows on the busiest
held expert over the mean of those held, per expert layer, averaged
over a step's expert layers; the median over the window's steps. From
the step's counters (``rows_per_expert``); 1.0 is an even load."""
from statistics import median


def read(run):
    steps = []
    for c in run.facts.get("counters") or []:
        layers = [max(r) * len(r) / sum(r)
                  for r in c.get("rows_per_expert", []) if sum(r)]
        if layers:
            steps.append(sum(layers) / len(layers))
    return median(steps) if steps else None
