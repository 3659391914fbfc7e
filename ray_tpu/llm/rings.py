"""A ring: a window layer's cache leaf ``[lines, slots, kv heads, window,
row]``, which does not grow with ``max_seq``. Position ``p`` lies in row ``p
% window``, so the ring holds the window that ends at the last position
written and is read whole: only a mask knows positions, and a ring's rows
need no order. What the serving modules with rings share
(llm/phi4flash_serving.py, llm/mimo_serving.py): which position a row
holds, and which of a prefill chunk's rows a ring is left.
"""

from __future__ import annotations

import jax.numpy as jnp


def ring_positions(end, window: int):
    """The position each row of a ring holds once ``end`` positions have
    been written: the largest one under ``end`` that falls on the row.
    Under 0: the row holds nothing of this sequence."""
    rows = jnp.arange(window)
    return end - 1 - jnp.mod(end - 1 - rows, window)


def ring_after_chunk(kv_len, n_valid, window: int, chunk: int):
    """A ring once a chunk at ``kv_len`` has left it its ``n_valid`` valid
    rows: (``fresh`` [1, window, 1], the rows that now hold a row of the
    chunk; ``source`` [window], which of the chunk's rows each takes)."""
    after = ring_positions(kv_len + n_valid, window)
    return ((after >= kv_len)[None, :, None],
            jnp.clip(after - kv_len, 0, chunk - 1))
