"""Operations and bytes of EvaByte's served path, from shapes: what a
decode step has to read, what the cache holds a slot, what EVA over a
prompt has to compute. Every function takes the adapter's plain
``sizes`` (``program_evabyte.sizes``). Kept with the benchmark so that no
PR that claims a gain can change how a utilisation is computed.
"""
from __future__ import annotations


def layer_params(sizes: dict) -> int:
    """One layer: q, k, v, o; the SwiGLU's three; two norms; EVA's two
    vectors a head."""
    h, f = sizes["hidden_size"], sizes["intermediate_size"]
    return 4 * h * h + 3 * h * f + 2 * h + 2 * h


def top_params(sizes: dict) -> int:
    """Embedding, the head's ``num_pred_heads`` x ``vocab_size`` columns
    and the final norm."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    return v * h + h * sizes["num_pred_heads"] * v + h


def held_params(sizes: dict) -> int:
    """Everything this share holds: its layers, embedding, head, norm."""
    return sizes["num_hidden_layers"] * layer_params(sizes) \
        + top_params(sizes)


def row_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """A cached row of one layer, an exact key and value or a summary's
    pooled pair alike: ``hidden_size`` lanes in each of two banks."""
    return 2 * sizes["hidden_size"] * dtype_bytes


def cache_bytes_per_slot(sizes: dict, page_size: int, max_context: int,
                         dtype_bytes: int = 2) -> int:
    """What one slot can hold, every layer: the ring of its window (a
    block window's own pages: it starts on a page) and a summary a chunk
    of ``max_context`` positions, in whole pages."""
    ring = -(-sizes["window_size"] // page_size)
    chunks = -(-max_context // sizes["chunk_size"])
    pages = ring + -(-chunks // page_size)
    return sizes["num_hidden_layers"] * pages * page_size \
        * row_bytes(sizes, dtype_bytes)


def decode_step_bytes(sizes: dict, window_rows: float, summary_rows: float,
                      live_rows: float = 0.0, dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: the weights it multiplies
    every row by (its layers, the final norm, the head's served
    ``vocab_size`` columns: head 0), one embedding row a live row, and
    the rows the softmax NEEDS, at their stored width in both banks,
    every layer: ``window_rows`` exact keys of the rows' own windows and
    ``summary_rows`` summaries of their closed windows, summed over the
    live rows."""
    h = sizes["hidden_size"]
    weights = sizes["num_hidden_layers"] * layer_params(sizes) + h \
        + h * sizes["vocab_size"]
    return float((weights + live_rows * h) * dtype_bytes
                 + (window_rows + summary_rows)
                 * sizes["num_hidden_layers"] * row_bytes(sizes, dtype_bytes))


def eva_pairs(sizes: dict, n: int) -> dict:
    """(query, key) pairs EVA's mathematics has over ``n`` positions from
    0, by part: ``window``, ``sum_t (t % W + 1)``; ``summary``, ``sum_t
    (t // W) * (W // C)``."""
    w, c = sizes["window_size"], sizes["chunk_size"]
    whole, rest = divmod(n, w)
    return {"window": whole * w * (w + 1) // 2 + rest * (rest + 1) // 2,
            "summary": (w // c) * (w * whole * (whole - 1) // 2
                                   + rest * whole)}


def eva_prefill_cost(sizes: dict, n: int, parts=("window", "summary"),
                     dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of one layer's EVA over ``n`` positions, for the
    ``parts`` a kernel computes: 4 x head_dim x heads operations for
    every (query, key) pair the mathematics has (scores and values, a
    multiply and an add each); q and the result once a part, the
    window's keys and values, the summaries' pooled pairs."""
    h = sizes["hidden_size"]
    pairs = eva_pairs(sizes, n)
    flops = 4.0 * h * sum(pairs[p] for p in parts)
    rows = 0
    if "window" in parts:
        rows += 4 * n                       # q, k, v, out
    if "summary" in parts:
        rows += 2 * n + 2 * (n // sizes["chunk_size"])   # q, out, k~, v~
    return flops, float(rows * h * dtype_bytes)
