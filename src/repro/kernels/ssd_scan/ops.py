"""jit'd public wrapper for the SSD scan kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax

from .. import interpret_mode
from .kernel import ssd_scan_pallas

__all__ = ["ssd_scan"]


@partial(jax.jit, static_argnames=("chunk", "head_block"))
def ssd_scan(xh, dt, A, Bc, Cc, chunk: int = 128, head_block: int = 0
             ) -> Tuple[jax.Array, Optional[jax.Array]]:
    y = ssd_scan_pallas(xh, dt, A, Bc, Cc, chunk=chunk,
                        head_block=head_block, interpret=interpret_mode())
    return y, None
