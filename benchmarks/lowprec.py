"""The lower precision the controls compute in (`references/*`,
`precision="lower"`)."""

from __future__ import annotations

import numpy as np


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even float32 -> bfloat16, returned as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + (np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
         ) & np.uint32(0xFFFF0000)  # finite inputs: the add cannot wrap
    return u.view(np.float32)
