"""The fused cross-entropy backward kernel's share of its roofline where
the head is passed twice a step (main head and MTP module, one weight:
two ``fused_ce_bwd`` calls) over a sliced vocabulary:
``fused_ce_bwd_roofline.train``'s own reader, which sums over whatever
calls the trace holds. The configuration's ``vocab_size`` is the VALID
rows, so they are the work; the rows the vocabulary is padded by are
masked in the kernel and count as none."""
import os

from benchmark import harness

read = harness.load_module(os.path.join(
    harness.HERE, "layer_metrics", "fused_ce_bwd_roofline.train.py")).read
