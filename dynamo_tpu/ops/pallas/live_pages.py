"""Per-row live page bounds of a paged KV cache, for the live-span decode
kernel (paged_attention.py). Plain XLA on [B]-sized operands: derived ONCE
per forward step and shared by every layer (docs/design_docs/engine.md).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def history_pcounts(
    start_pos: jnp.ndarray, block_size: int, table_width: int
) -> jnp.ndarray:
    """Per-row count of the pages that hold keys before ``start_pos``,
    clamped to the table width so a row can never index past its table
    (the causal mask already hides any positions beyond it)."""
    start32 = start_pos.astype(jnp.int32)
    return jnp.minimum((start32 + block_size - 1) // block_size, table_width)


def window_page_bounds(
    start_pos: jnp.ndarray, window, block_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(wlo, poff) for a sliding-window layer: ``wlo[b]`` is the first
    VISIBLE history key index (``max(0, pos − W + 1)``; 0 when the layer
    is full-attention) and ``poff[b] = wlo // BS`` its page — where each
    row's dynamic page loop STARTS, so a windowed row streams only pages
    holding in-window keys. The boundary page (``pos − W`` mid-page) is
    streamed and masked in-kernel via the same ``wlo``. ``window`` may be
    a TRACED scalar (0 = full) so one compiled program serves Gemma-3's
    local/global layer mix."""
    start32 = start_pos.astype(jnp.int32)
    w = jnp.asarray(window, jnp.int32)
    wlo = jnp.where(w > 0, jnp.maximum(start32 - w + 1, 0), 0)
    return wlo, wlo // block_size


def live_page_bounds(start_pos, chunk_lens, C, window, block_size, table_width):
    """(pcount, poff) per row for the live-span decode kernel: the row's
    keys live on pages ``[poff, pcount)``. The cache already holds the
    chunk, so the last needed key is ``start + C − 1``; a row with
    ``chunk_lens == 0`` (an empty slot) gets ``pcount`` 0 and costs
    nothing. ``poff`` is the page of the first key a sliding window
    leaves visible to the chunk's first query (0 without a window)."""
    start32 = start_pos.astype(jnp.int32)
    pcount = history_pcounts(start32 + C, block_size, table_width)
    if chunk_lens is not None:
        pcount = jnp.where(chunk_lens > 0, pcount, 0)
    _, poff = window_page_bounds(start32, window, block_size)
    return pcount, poff


def live_work_list(pcount, poff, group_pages: int, table_width: int):
    """(total, step_row, step_page): the flat list of (row, first page)
    steps that covers every row's live pages ``[poff, pcount)`` in groups
    of ``group_pages``, rows in order, and its length ``total``.

    Invariant the kernel's grid rests on: the lists are ONE entry longer
    than the most steps the grid can take (the static worst case, every
    row full), and the entries at or past ``total`` repeat the last step.
    So a list never has one entry only — a one-entry list (one row under a
    table no wider than a group) halts the v5e core inside the custom
    call, while the same one-step grid over a list of two runs — and the
    entry at ``total``, which the pipeline's index maps may read one step
    ahead of the grid's last step, exists and names a block it already
    holds (my chip runs, PR 25: docs/design_docs/engine.md, "The work
    list is never one entry")."""
    S = group_pages
    B = pcount.shape[0]
    groups = (jnp.maximum(pcount - poff, 0) + S - 1) // S  # [B]
    ends = jnp.cumsum(groups)
    begins = ends - groups
    total = ends[-1]
    length = B * (-(-table_width // S)) + 1
    t = jnp.minimum(jnp.arange(length, dtype=jnp.int32), total - 1)[:, None]
    # Step t belongs to the one row with begins <= t < ends: masked sums
    # over [T, B] instead of gathers (one fusion on the TPU).
    mine = (begins[None, :] <= t) & (t < ends[None, :])
    rows = jnp.arange(B, dtype=jnp.int32)[None, :]
    step_row = jnp.sum(jnp.where(mine, rows, 0), axis=1)
    step_page = jnp.sum(
        jnp.where(mine, poff[None, :] + (t - begins[None, :]) * S, 0), axis=1
    )
    return total, step_row, step_page
