"""What the families share."""

from __future__ import annotations

import numpy as np


def relative_difference(got, ref) -> float:
    """|got - ref| / |ref| in float64 (Euclidean norm over all entries)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-300))
