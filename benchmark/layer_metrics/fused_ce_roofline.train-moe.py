"""The fused cross-entropy kernels' share of their roofline where the
head is passed twice a step (main head and MTP module, one weight) over
a sliced vocabulary: ``fused_ce_roofline.train``'s own reader, which
sums over whatever calls the trace holds. The configuration's
``vocab_size`` is the VALID rows, so they are the work; the rows the
vocabulary is padded by are masked in the kernel and count as none."""
import os

from benchmark import harness

read = harness.load_module(os.path.join(
    harness.HERE, "layer_metrics", "fused_ce_roofline.train.py")).read
