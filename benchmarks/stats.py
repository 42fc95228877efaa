"""The benchmark's percentile: numpy's default (linear interpolation
between the two nearest ranks), None for no samples."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None
