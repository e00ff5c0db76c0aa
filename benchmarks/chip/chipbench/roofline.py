"""The yardstick's arithmetic: the chip's peaks, and the operations and
bytes a call needs, computed from shapes. Nothing here reads a device.

Peaks live in ``peaks.json`` beside this package, keyed by JAX's
``device_kind``; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, pk: dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# -- model FLOPs (dense decoder; attention over each token's real context) --

def layer_matmul_params(m: dict) -> int:
    """Weights one decoder layer multiplies by, per token."""
    d, h, kh, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff


def decode_flops(m: dict, n_layers: int, context: float) -> float:
    """One token through ``n_layers`` layers and one unembedding head,
    attending over ``context`` real positions (QK^T and PV)."""
    attn = 4.0 * n_layers * context * m["n_heads"] * m["head_dim"]
    return (2.0 * n_layers * layer_matmul_params(m) + attn
            + 2.0 * m["vocab"] * m["d_model"])


def prefill_flops(m: dict, prompt_len: int) -> float:
    """A whole prompt through every layer (causal attention counted over
    the lower triangle), and the final head at its last position."""
    n, s = m["n_layers"], prompt_len
    attn = 2.0 * n * s * s * m["n_heads"] * m["head_dim"]
    return (2.0 * n * layer_matmul_params(m) * s + attn
            + 2.0 * m["vocab"] * m["d_model"])


# -- kernel bytes (both kernels move data; their FLOPs are negligible) ----

def paged_gather_append_bytes(rows: int, max_pages: int, page: int,
                              features: int, itemsize: int,
                              live_pages: float) -> float:
    """One call over both pools (K and V): write every row's gathered
    (max_pages, page, F) block, which the call's output shape requires, and
    read each row's ``live_pages`` pages (the mean over the call's rows;
    null pages repeat one block, which the kernel's pipeline fetches once),
    plus the appended row. Rewriting unchanged pool pages in place is not
    counted: the algorithm does not need it."""
    page_bytes = page * features * itemsize
    return 2 * (rows * (max_pages + live_pages) * page_bytes
                + rows * features * itemsize)


def exit_decision_bytes(rows: int, vocab: int, logit_itemsize: int = 4) -> int:
    """Read the (rows, V) logits once; write mask, pred and confidence."""
    return rows * vocab * logit_itemsize + rows * (1 + 4 + 4)


def scatter_merge_bytes(ring_rows: int, row_bytes: int) -> int:
    """Merge payload rows into a ring slab: read and write the ring row for
    every ring slot (the payload row a slot copies is not counted: slots
    past the enqueue all name the same row, fetched once)."""
    return 2 * ring_rows * row_bytes
