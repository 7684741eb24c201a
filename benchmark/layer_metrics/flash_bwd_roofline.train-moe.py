"""The one-kernel flash-attention backward's share of its roofline under
latent attention (q, k and v all ``qk_nope + qk_rope`` = ``v_head_dim``
wide): ``flash_bwd_roofline.train``'s own reckoning (the ``flash_bwd``
calls by name, five matmuls a call), with the facts
``mla_flash_roofline.train-moe`` takes."""
import os

from benchmark import harness

_bwd = harness.load_module(os.path.join(
    harness.HERE, "layer_metrics", "flash_bwd_roofline.train.py"))


def read(run):
    f = run.facts
    s = f["sizes"]
    return _bwd.share(run, s["num_attention_heads"] // f["tensor"],
                      s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
