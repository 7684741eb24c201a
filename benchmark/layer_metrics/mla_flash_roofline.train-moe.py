"""The flash-attention kernels' share of their roofline under latent
attention (q, k and v all ``qk_nope + qk_rope`` = ``v_head_dim`` wide):
the least time the chip could take for the calls the trace holds
(forward, dQ and dK/dV each by ``rooflines.flash_call_cost``) over the
summed device time of those calls, on the busiest device.

The kernels are found by the names their ``pallas_call`` carries
(``ops/flash_attention.py``: ``flash_fwd``, ``flash_dq``, ``flash_dkv``),
searched for in the instruction's own name, left of `` = ``; jax wraps
a name in the transforms the call went through. The forward's
recomputed copy counts as the kernels' own work, as in
``flash_attn_roofline.train``."""
from benchmark import rooflines

KINDS = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}


def kernel_of(event_name: str):
    head = event_name.split(" = ")[0]
    if "flash_ring" in head:
        return None
    return next((kind for name, kind in KINDS.items() if name in head), None)


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    s = f["sizes"]
    heads = s["num_attention_heads"] // f["tensor"]
    width = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    least = actual = 0.0
    for name, start, end in dev["ops"]:
        kind = kernel_of(name)
        if kind is None:
            continue
        flops, nbytes = rooflines.flash_call_cost(
            kind, f["rows_per_replica"], f["seq"], heads, width)
        least += rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
        actual += (end - start) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
