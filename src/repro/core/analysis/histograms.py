"""Distribution summaries (path-length histograms etc.).

The hot path — histogramming a full (n, n) distance matrix — goes through the
Pallas segment-histogram kernel; numpy bincount is the oracle fallback.
"""
from __future__ import annotations

from typing import List

import numpy as np
import jax.numpy as jnp

from ... import transfers

__all__ = ["path_length_histogram"]


def path_length_histogram(dist: np.ndarray, max_len: int = 64,
                          use_kernel: bool = True) -> List[int]:
    """Counts of finite off-diagonal path lengths 1..max_len."""
    if use_kernel:
        from ... import kernels

        d = transfers.upload(dist, "histograms", "histogram_dist",
                             jnp.float32)
        counts = kernels.ops.value_histogram(d, num_bins=max_len + 1)
        counts = transfers.download(transfers.wait(counts, "histograms"),
                                    "histograms", "histogram_counts")
    else:
        finite = dist[np.isfinite(dist)].astype(np.int64)
        counts = np.bincount(finite, minlength=max_len + 1)[: max_len + 1]
    counts = counts.tolist()
    counts[0] = 0  # drop the diagonal zeros
    # trim trailing zeros
    while len(counts) > 1 and counts[-1] == 0:
        counts.pop()
    return counts
